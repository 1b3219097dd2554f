"""The harness that judges a 10^9-parameter cell, in the lane the
driver runs: ``correct.compare``'s one walk over the leaves against the
whole-tree arithmetic it replaced, and the FedAvg reference's
three-tree round against the one it replaced; and ``tag_reduce``'s
seconds of a named part of a model scope. The cases live with the
benchmark (``benchmark/tests``, which no lane of the driver collects)
and are imported here, not copied: CPU only, seconds."""
import gc
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.tests.test_compare import (  # noqa: E402,F401
    test_no_whole_tree_in_float64,
    test_one_round_followed_reads_the_same_tree_twice,
    test_one_walk_gives_the_whole_tree_numbers,
    test_trees_are_let_go_of_as_the_rounds_come,
)
from benchmark.tests.test_fedavg_reference import (  # noqa: E402,F401
    test_at_most_three_trees_on_the_device,
    test_round_is_bit_identical_to_the_one_it_replaced,
)
from benchmark.tests.test_tag_reduce import (  # noqa: E402,F401
    test_none_where_no_operation_holds_the_tag,
    test_reader_returns_none_without_a_trace_or_a_profile,
    test_seconds_of_the_operations_that_hold_the_tag,
)


@pytest.fixture(autouse=True)
def _no_garbage_from_the_test_before():
    """Several of these cases count what is live on the device or on
    the host: an earlier test's uncollected cycles, freed in the middle
    of one, would read as negative bytes."""
    gc.collect()
