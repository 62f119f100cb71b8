"""The lower-precision control comes out as not correct.

The control is the reference put in the program's place with every matrix
product in fp8 (the step below the bf16 the configurations state): at
each served position it judges the token the fp8 forward puts first.
Each case drives a whole run of a cell at a size the CPU holds (output
heads untied: see ``bench_tree.tiny_tree``) and reads the control over the
same sample of served tokens: the run is correct and the control is not.  The readings at the cells' own sizes, on the chip,
are in PERF.md."""
import pytest

import bench_tree

CELLS = ["minicpm-2b.streams", "codeqwen1.5-7b.tp4.chat",
         "minicpm-2b.prefill"]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return bench_tree.tiny_tree(tmp_path_factory.mktemp("bench"),
                               untied=True)


@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 + 9])
@pytest.mark.parametrize("workload", CELLS)
def test_fp8_control_fails_the_check(tree, workload, seed):
    res = bench_tree.run_cell(tree, workload, seed=seed, control=1)
    limit = res["checks"]["worst_gap_sigma"]["limit"]
    assert res["correct"], res["checks"]
    assert not res["control"]["correct"], (res["control"], limit)
    assert res["control"]["worst_gap_sigma"] > 3 * \
        res["checks"]["worst_gap_sigma"]["value"]
