"""The sparse configuration's reference and operation counts, in the
lane the driver runs: ``benchmark/reference/keye_vl2.py`` against a
case written out by hand and ``benchmark/flops/keye_vl2.py`` against
counts written out, and the by-hand run that asks every reader
(``benchmark/all_readers.py``). The cases live with the benchmark
(``benchmark/tests``, which no lane of the driver collects) and are
imported here, not copied: CPU only, seconds."""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.tests.test_all_readers import (  # noqa: E402,F401
    test_every_reader_is_asked_and_the_rows_are_kept,
)
from benchmark.tests.test_keye_flops import (  # noqa: E402,F401
    test_counts_of_the_configuration_written_out,
    test_what_bounds_each_piece_at_the_cells_length,
)
from benchmark.tests.test_keye_reference import (  # noqa: E402,F401
    test_one_layer_written_out_by_hand,
    test_specification_is_read_from_the_configurations_file,
    test_the_control_hook_reaches_every_product,
)
