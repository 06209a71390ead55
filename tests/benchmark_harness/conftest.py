"""``bench_root``: the benchmark a structural test reads, twice — the tree's
own, and a copy to which a synthetic fifth configuration, cell, ``out_tok_s``
listing and per-layer metric have been appended (``bh_util.add_fifth_cell``).
A test that holds a list of BENCHMARK.json closed fails the second case:
the next configuration's PR appends just that, and may not edit the test."""
import pytest
from bh_util import REPO, add_fifth_cell, copy_benchmark


@pytest.fixture(scope="session", params=["tree", "fifth_cell_appended"])
def bench_root(request, tmp_path_factory) -> str:
    if request.param == "tree":
        return REPO
    root = copy_benchmark(tmp_path_factory.mktemp("fifth"))
    add_fifth_cell(root)
    return root
