"""The looped configuration's reference and operation counts, in the
lane the driver runs: ``benchmark/reference/ouro.py`` against a
two-pass case unrolled by hand and ``benchmark/flops/ouro.py`` against
counts written out. The cases live with the benchmark
(``benchmark/tests``, which no lane of the driver collects) and are
imported here, not copied: CPU only, seconds."""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.tests.test_ouro_flops import (  # noqa: E402,F401
    test_both_pieces_are_bound_by_the_products_at_the_cells_length,
    test_counts_of_the_configuration_written_out,
)
from benchmark.tests.test_ouro_reference import (  # noqa: E402,F401
    test_specification_is_read_from_the_configurations_file,
    test_the_control_hook_reaches_every_product,
    test_two_passes_unrolled_by_hand,
)
