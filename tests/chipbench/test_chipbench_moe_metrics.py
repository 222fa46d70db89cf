"""The readers of the MoE counters and of the grouped matmul's roofline,
on a synthetic trace context and the program's own tracer: nothing
where the program emits no counters or ran no such kernel."""
import pytest

from chipbench import cell as C
from chipbench import moe_counts
from chipbench import run as RUN
from chipbench import trace_reduce as TR
from repro.obs import tracer

CFG = {"hybrid_override_pattern": "MEMEM*EMEM", "num_hidden_layers": 7,
       "hidden_size": 2688, "moe_intermediate_size": 1856,
       "experts_held": 8}
PEAKS = {"flops": 197e12, "hbm_bytes_per_s": 819e9}


def ctx(ops=(), modules=(), steps=2, share=1.0):
    red = TR.Reduction(window_s=1.0, busy_s=1.0, ops=list(ops),
                       modules=list(modules), gaps=[], devices=1)
    return RUN.TraceContext(
        steps=steps, clients=2, chips=1, cfg=CFG, reduction=red, spans=[],
        wire_bytes=0.0, wire_rows=None, distill_share=share,
        flops_per_step=0.0, peaks=PEAKS)


def reader(name):
    return C.load_module(C.ROOT, "metrics", name).read


@pytest.fixture
def counted():
    """A traced session in which 4 client steps counted their rows."""
    tracer.enable()
    for rows, top in ((4608, 720), (4600, 700), (4620, 760), (4612, 690)):
        tracer.counter("moe/rows_held", rows, client=0, step=0)
        tracer.counter("moe/max_expert_rows", top, client=0, step=0)
        tracer.counter("runtime/other", 1.0)
    tracer.disable()
    yield
    tracer.enable()
    tracer.disable()


def test_counters_are_read_once_tracing_is_off(counted):
    assert moe_counts.counter_values("moe/rows_held") == [4608, 4600, 4620,
                                                          4612]
    assert moe_counts.counter_values("moe/none") is None


def test_load_imbalance_is_the_busiest_expert_over_an_even_split(counted):
    got = reader("moe_load_imbalance")(ctx())
    assert got == pytest.approx((720 + 700 + 760 + 690)
                                / ((4608 + 4600 + 4620 + 4612) / 8))


def test_gmm_roofline_counts_only_the_updates_calls(counted):
    update = TR.Event("jit_update", 0.0, 0.5)
    ops = [TR.Event("moe_gmm.3", 0.01, 0.012, True),
           TR.Event("transpose_jvp_jit_moe_gmm___.2", 0.02, 0.022, True),
           TR.Event("moe_tgmm.1", 0.03, 0.032, True),
           TR.Event("moe_gmm.4", 0.6, 0.7, True),  # a publish forward's
           TR.Event("fusion.1", 0.04, 0.05)]
    got = reader("moe_gmm_roofline")(ctx(ops, [update]))
    # 4 client steps of 2 passes through 3 MoE layers: 768.75 rows a pass
    rows = (4608 + 4600 + 4620 + 4612) / 24
    least = max(moe_counts.gmm_ops(rows, 2688, 1856) / 197e12,
                moe_counts.gmm_bytes(rows, 8, 2688, 1856) / 819e9)
    assert got == pytest.approx(100.0 * least * 3 / 0.006)


def test_readers_report_nothing_without_counters_or_kernels():
    tracer.enable()
    tracer.disable()
    assert reader("moe_load_imbalance")(ctx()) is None
    assert reader("moe_gmm_roofline")(ctx()) is None


def test_moe_client_step_counts_its_rows_and_emits_them():
    """A tiny Nemotron-H fleet: each client step reports the pairs routed
    to its held experts and the busiest one's, and emits both as counters
    with the values the host read."""
    fam = C.load_module(C.ROOT, "families", "nemotron_h")
    cell = C.Cell("nemotron3_nano_fleet2.distill", 5, overrides=fam.TINY)
    algo = cell.build()
    tracer.enable()
    try:
        metrics = algo.step(0)
    finally:
        tracer.disable()
    cfg = cell.cfg
    tokens = cell.traffic["seq_len"]  # batch 1 + 1, both passes
    layers = fam.pattern(cfg).count("E")
    for i in range(cell.clients):
        rows = metrics[f"c{i}/moe/rows_held"]
        top = metrics[f"c{i}/moe/max_expert_rows"]
        assert 0 < rows <= 2 * layers * tokens * cfg["num_experts_per_tok"]
        assert rows / cfg["experts_held"] <= top <= rows
    vals = moe_counts.counter_values("moe/rows_held")
    assert sorted(vals) == sorted(metrics[f"c{i}/moe/rows_held"]
                                  for i in range(cell.clients))
