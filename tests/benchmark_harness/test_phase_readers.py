"""The eight per-layer metrics that read the engine's phase times and
dispatch counters (PR 24; the ninth, ``decode_step_ms``, was retired by
PR 52), each on a hand-made ``ctx`` with a known answer, and None where the
program has no such counter (every earlier commit) or the run was not
traced — once for every cell BENCHMARK.json declares the reader in, under
that cell's own configuration. The two rooflines divide counters by a
kernel's traced time: they take the counters over the traced slice (PR 33),
the others over the window."""
import pytest
from bh_util import cell_config, declared_pairs, read_metric

BEFORE = {"ns_admit": 1_000, "ns_prefill_build": 0, "ns_prefill_device": 0,
          "ns_decode_device": 5_000, "ns_telemetry": 0, "ns_loop_other": 0,
          "ns_loop_idle": 7_000, "max_ns_admit": 900,
          "admitted": 2, "queue_wait_ns": 0, "first_tokens": 1,
          "prefill_span_ns": 1_000_000, "decode_dispatches": 10,
          "decode_live_slots": 40, "decode_live_pages": 1_000,
          "decode_steps": 25, "prefill_rows_live": 3,
          "prefill_rows_padded": 4, "prefill_tokens": 300,
          "prefill_ctx_pages": 100, "prefill_attn_pairs": 10_000,
          "clock_ns": 5, "mesh": None}
# deltas: admit 4 ms, prefill build 6 ms, prefill device 30 ms, decode
# device 50 ms, telemetry 2 ms, loop other 8 ms, idle 900 ms;
# 4 admitted, 12 ms queued, 5 first tokens, 250 ms admit -> first token;
# 20 decode dispatches with 160 live slots and 38,400 live pages over 100
# device steps; 30 prefill rows live of 40 run, with 3,600 tokens (120 a
# row), 3,000 context pages (100 a row) and 6,000,000 pairs (200,000 a row)
AFTER = {"ns_admit": 4_001_000, "ns_prefill_build": 6_000_000,
         "ns_prefill_device": 30_000_000, "ns_decode_device": 50_005_000,
         "ns_telemetry": 2_000_000, "ns_loop_other": 8_000_000,
         "ns_loop_idle": 900_007_000, "max_ns_admit": 3_000_000,
         "admitted": 6, "queue_wait_ns": 12_000_000, "first_tokens": 6,
         "prefill_span_ns": 251_000_000, "decode_dispatches": 30,
         "decode_live_slots": 200, "decode_live_pages": 39_400,
         "decode_steps": 125, "prefill_rows_live": 33,
         "prefill_rows_padded": 44, "prefill_tokens": 3_900,
         "prefill_ctx_pages": 3_100, "prefill_attn_pairs": 6_010_000,
         "clock_ns": 1_000_000_005, "mesh": None}
DOCQA = "docqa-sessions-1chip"
# the decode shape (query window 1) ran 400 calls in 0.8 s: 2 ms a call, 40
# ms a step of doc-QA's 20 layers; the prefill shape (a window above 1) ran 60 calls
# in 0.3 s: 5 ms a call; each reader must leave the other's shape out
# the slice's own counters (the snapshots ``trace_stop`` returns beside the
# reduction): 10 decode dispatches with 24,000 live pages (2,400 a dispatch,
# where the window's mean is 1,920); 6 prefill rows live of 8 run, with 720
# tokens (120 a row), 600 context pages (100 a row) and 1,500,000 pairs
# (250,000 a row, where the window's mean is 200,000)
SLICE_BEFORE = {"decode_dispatches": 20, "decode_live_pages": 30_000,
                "prefill_rows_live": 20, "prefill_rows_padded": 30,
                "prefill_tokens": 3_000, "prefill_ctx_pages": 2_000,
                "prefill_attn_pairs": 4_000_000, "clock_ns": 7}
SLICE_AFTER = {"decode_dispatches": 30, "decode_live_pages": 54_000,
               "prefill_rows_live": 26, "prefill_rows_padded": 38,
               "prefill_tokens": 3_720, "prefill_ctx_pages": 2_600,
               "prefill_attn_pairs": 5_500_000, "clock_ns": 400_000_007}
TRACE = {"devices": 1, "busy_s": 1.0, "window_s": 1.1, "ops": [
    ["ragged_paged_attention:bf16[32,1,32,128]", 0.8, 400, "tpu_custom_call"],
    ["ragged_paged_attention:bf16[1,128,32,128]", 0.3, 60, "tpu_custom_call"],
    ["fusion:bf16[32,14336]", 0.1, 400, ""]],
    "stats_before": SLICE_BEFORE, "stats_after": SLICE_AFTER}


def _ctx(cell=DOCQA, in_slice=(SLICE_BEFORE, SLICE_AFTER), **over):
    ctx = {"stats_before": BEFORE, "stats_after": AFTER,
           "config": cell_config(cell),
           "trace": dict(TRACE, stats_before=in_slice[0],
                         stats_after=in_slice[1]),
           "device": {"kind": "TPU v5 lite"}, "rehearse": False}
    ctx.update(over)
    return ctx


_read = read_metric


def _head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg[
        "num_attention_heads"]


def roofline(cfg: dict) -> float:
    """The slice's 2400 live pages a dispatch x 16 tokens x the keys and
    values of every layer (doc-QA: 81,920 B a token = 3.1457 GB, 3.8410 ms
    at 819 GB/s), over 2 ms a call x the layers a step (doc-QA: 40 ms)."""
    layers = cfg["num_hidden_layers"]
    kv = 2 * layers * cfg["num_key_value_heads"] * _head_dim(cfg) * 2
    return 100.0 * (2400 * 16 * kv / 819e9) / (0.002 * layers)


def prefill_roofline(cfg: dict) -> float:
    """A live row and layer of the slice: 4 x heads x head_dim x 250,000
    pairs (doc-QA: 4.096 GFLOP = 20.79 us at 197 TFLOP/s; its 100 pages x 16
    x 4,096 B of keys and values + 120 tokens x 32 x 128 x 2 B read and
    written = 8.5197 MB = 10.40 us at 819 GB/s: the compute bound), over
    5 ms a call x 8 / 6 rows run per live row."""
    fl = 4 * cfg["num_attention_heads"] * _head_dim(cfg) * 250_000
    return 100.0 * (fl / 197e12) / (0.005 * 8 / 6)


# a number, or what it is under a cell's configuration
EXPECTED = {
    # host: 4 + 6 + 2 + 8 = 20 ms of 100 ms worked; the idle 900 ms and
    # the max_ns_* keys stay out
    "engine_host_share": 20.0,
    "admit_ms_per_request": 1.0,
    "queue_wait_ms": 3.0,
    "prefill_span_ms": 50.0,
    # 8 slots a dispatch of the cell's ``max_batch_size``
    "decode_slot_occupancy":
        lambda cfg: 100.0 * 8 / cfg["engine"]["max_batch_size"],
    "ragged_decode_roofline": roofline,
    "prefill_row_fill": 75.0,           # 30 of 40 rows
    "ragged_prefill_roofline": prefill_roofline,
}
TRACED = ("ragged_decode_roofline", "ragged_prefill_roofline")
PAIRS = declared_pairs(names=EXPECTED)


def _expected(name: str, cell: str) -> float:
    want = EXPECTED[name]
    return want(cell_config(cell)) if callable(want) else want


def test_the_rooflines_of_the_docqa_cell_by_hand():
    cfg = cell_config(DOCQA)
    assert 2400 * 16 * 81_920 / 819e9 / 0.040 * 100 == pytest.approx(
        roofline(cfg)) and 9.5 < roofline(cfg) < 9.7
    assert 0.31 < prefill_roofline(cfg) < 0.32
    assert _expected("decode_slot_occupancy", DOCQA) == 25.0


@pytest.mark.parametrize("name,cell", PAIRS)
def test_reader_gives_the_hand_computed_value(name, cell):
    assert _read(name, _ctx(cell)) == pytest.approx(
        _expected(name, cell), rel=1e-9)


@pytest.mark.parametrize("name,cell", PAIRS)
def test_reader_gives_none_on_a_program_without_the_counters(name, cell):
    """The parent commit's ``engine.stats`` has none of these keys: the
    reader returns None, it does not raise."""
    old = {"decode_dispatches": 10, "tokens_out": 7, "mesh": None}
    new = dict(old, decode_dispatches=30)
    assert _read(name, _ctx(cell, (old, new), stats_before=old,
                            stats_after=new)) is None
    assert _read(name, _ctx(cell, (None, None), stats_before=None,
                            stats_after=None)) is None


@pytest.mark.parametrize("name,cell", PAIRS)
def test_reader_gives_none_when_nothing_was_counted(name, cell):
    assert _read(name, _ctx(cell, (SLICE_BEFORE, SLICE_BEFORE),
                            stats_after=BEFORE)) is None


@pytest.mark.parametrize("name", TRACED)
def test_roofline_needs_the_trace_and_its_own_shape(name):
    assert _read(name, _ctx(trace=None)) is None
    assert _read(name, _ctx(trace={"devices": 0})) is None
    # a rehearsal on the CPU: snapshots and no device plane
    assert _read(name, _ctx(trace={
        "devices": 0, "stats_before": SLICE_BEFORE,
        "stats_after": SLICE_AFTER})) is None
    # a trace without the slice's snapshots (the parent's ``trace_stop``):
    # None, never the window's counters over the slice's kernel time
    bare = {k: v for k, v in TRACE.items() if not k.startswith("stats_")}
    assert _read(name, _ctx(trace=bare)) is None
    assert _read(name, _ctx(trace=dict(bare, stats_before=SLICE_BEFORE))) \
        is None
    other = [op for op in TRACE["ops"] if (",1,32," in op[0]) ==
             (name == "ragged_prefill_roofline")]
    assert _read(name, _ctx(trace=dict(TRACE, ops=other))) is None
    assert _read(name, _ctx(rehearse=True)) is None


def test_the_others_read_counters_alone():
    """Over the window, whatever the slice's snapshots say."""
    for name in sorted(set(EXPECTED) - set(TRACED)):
        want = _expected(name, DOCQA)
        assert _read(name, _ctx(trace=None)) == pytest.approx(want)
        assert _read(name, _ctx(in_slice=(AFTER, AFTER))) == pytest.approx(
            want)


def test_slice_deltas_are_the_traces_own_snapshots():
    from benchmarks.layer_metrics import _engine
    ctx = _ctx()
    assert _engine.slice_deltas(ctx)["decode_live_pages"] == 24_000
    assert _engine.deltas(ctx)["decode_live_pages"] == 38_400
    assert _engine.per(ctx, "decode_live_pages", "decode_dispatches",
                       over=_engine.slice_deltas) == 2400.0
    assert _engine.per(ctx, "decode_live_pages", "decode_dispatches") == 1920.0
    assert _engine.slice_deltas({"trace": None}) == {}
    assert _engine.slice_deltas({}) == {}


def test_prefill_roofline_takes_the_memory_bound_where_it_is_larger():
    """Rows of a few tokens over a long cache stream more than they
    compute: 20,000 pairs a row is 1.66 us of FLOPs against 10.40 us of
    bytes."""
    after = dict(SLICE_AFTER, prefill_attn_pairs=SLICE_BEFORE[
        "prefill_attn_pairs"] + 6 * 20_000)
    least = (100 * 16 * 4096 + 2 * 120 * 32 * 128 * 2) / 819e9
    assert _read("ragged_prefill_roofline",
                 _ctx(in_slice=(SLICE_BEFORE, after))) == \
        pytest.approx(100.0 * least / (0.005 * 8 / 6), rel=1e-9)
