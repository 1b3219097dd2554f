from fedtorch_tpu.utils.checkpoint import (  # noqa: F401
    AsyncCheckpointer, get_checkpoint_folder_name, init_checkpoint_dir,
    maybe_resume, save_checkpoint,
)
from fedtorch_tpu.utils.diagnostics import (  # noqa: F401
    aggregation_tracking, check_finite, model_norms,
)
from fedtorch_tpu.utils.logging import RunLogger  # noqa: F401
from fedtorch_tpu.utils.meters import (  # noqa: F401
    AverageMeter, PhaseTimer, define_local_training_tracker,
    define_val_tracker,
)
from fedtorch_tpu.utils.compile_cache import (  # noqa: F401
    enable_compile_cache, jit_cache_size,
)
from fedtorch_tpu.utils.lock_sentinel import (  # noqa: F401
    LockOrderSentinel, active_sentinel,
)
from fedtorch_tpu.utils.platform import (  # noqa: F401
    device_stamp, require_tpu,
)
from fedtorch_tpu.utils.tracing import (  # noqa: F401
    RecompilationSentinel, instrument_trace, trace_counts,
)
