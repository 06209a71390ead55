"""The exact command of BENCHMARK.json, rehearsed on the CPU at toy sizes
(the program takes its jnp path off the chip): the control flow of the
serving cell (traced), of the training cell, of the pending open-loop cell,
and of the pending four-chip cell's mesh on four forced host devices. A
rehearsal's metrics are all null and its device says cpu."""
import pytest
from bh_util import (FIFTH_METRIC, LAST_LINE_KEYS, add_fifth_cell, add_pending,
                     copy_benchmark, rehearse)


def _check(line: dict, names: set) -> None:
    assert LAST_LINE_KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert names <= set(line["metrics"])
    # a number from a CPU run is never written under a device metric's name
    assert all(m["value"] is None for m in line["metrics"].values())


@pytest.mark.parametrize("workload,trace,names", [
    # ``router_wait_ms``: the front path's series came through the door
    # (``serve_child``'s ``stats`` event -> ``ctx["serve_summary"]``) and
    # their count covers the run's requests
    ("docqa-sessions-1chip", 1, {"prefix_hit_tok_share", "ttft_cold_ms",
                                 "decode_tok_per_dispatch",
                                 "front_overhead_ms", "router_wait_ms"}),
    ("pretrain-4k-1chip", 0, {"train_tok_s", "setup_s"}),
])
def test_cell_rehearses(workload, trace, names):
    _check(rehearse(workload, trace=trace), names)


def test_existing_cell_rehearses_beside_an_appended_fifth(tmp_path):
    """The next configuration's PR appends a configuration, a cell, an
    ``out_tok_s`` listing and a per-layer metric (``bh_util.add_fifth_cell``,
    here with doc-QA among the cells the new metric lists): the cell that
    was there runs as it did, with the new metric in its line."""
    root = copy_benchmark(tmp_path)
    add_fifth_cell(root, also=("docqa-sessions-1chip",))
    line = rehearse("docqa-sessions-1chip", root=root, trace=1)
    _check(line, {"prefix_hit_tok_share", "decode_slot_occupancy",
                  FIFTH_METRIC})
    # a rehearsal has no device trace: a reader that needs the traced
    # slice finds nothing to read and is left out of a well-formed line
    assert not {"ragged_decode_roofline", "ragged_prefill_roofline",
                "decode_prog_dev_ms"} & set(line["metrics"])


def test_pending_chat_cell_rehearses(tmp_path):
    root = copy_benchmark(tmp_path)
    add_pending(root, "chat-open-1chip")
    _check(rehearse("chat-open-1chip", root=root),
           {"ttft_p90_ms", "tpot_p90_ms", "setup_s"})


def test_tp4_cell_rehearses_on_four_host_devices(tmp_path):
    root = copy_benchmark(tmp_path)
    add_pending(root, "chat-open-1chip")
    add_pending(root, "chat-open-tp4")
    line = rehearse("chat-open-tp4", root=root, trace=1)
    _check(line, {"mesh_reshard_bytes", "chat_decode_tok_per_dispatch"})
    assert line["device"]["count"] >= 4
