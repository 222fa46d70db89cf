"""Tests for the §Perf optimization paths: chunked CE, iterative top-k,
expert-parallel fallback, the distributed MHD step, and the sparse-teacher
CE of the top-k wire format."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.mhd import MHDConfig
from repro.core.mhd_distributed import (
    DistributedMHDConfig,
    _dense_xent_and_conf,
    _sparse_xent_and_conf,
    _topk_iterative,
    _topk_pack,
    make_distributed_mhd_step,
)
from repro.models.transformer import _chunked_xent, softmax_xent


@pytest.mark.parametrize("B,V,k", [(3, 100, 5), (2, 257, 8), (1, 64, 64)])
def test_topk_iterative_matches_lax(B, V, k):
    x = jax.random.normal(jax.random.PRNGKey(0), (B, V))
    v, i = _topk_iterative(x, k)
    v_r, i_r = jax.lax.top_k(x, k)
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_r), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_r))


@pytest.mark.parametrize("B,T,V,chunk", [(3, 17, 11, 5), (2, 16, 33, 8),
                                         (1, 7, 9, 16)])
def test_chunked_xent_matches_dense(B, T, V, chunk):
    h = jax.random.normal(jax.random.PRNGKey(0), (B, T, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, V))
    lab = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, V)
    dense = softmax_xent(jnp.einsum("btd,dv->btv", h, w), lab)
    ch = _chunked_xent(h, w, lab, chunk=chunk)
    np.testing.assert_allclose(float(ch), float(dense), rtol=1e-5)


def test_chunked_xent_gradients_match():
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 20))
    lab = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0, 20)
    g_dense = jax.grad(lambda w_: softmax_xent(
        jnp.einsum("btd,dv->btv", h, w_), lab))(w)
    g_chunk = jax.grad(lambda w_: _chunked_xent(h, w_, lab, 4))(w)
    np.testing.assert_allclose(np.asarray(g_chunk), np.asarray(g_dense),
                               rtol=1e-4, atol=1e-6)


def test_moe_a2a_falls_back_to_scatter_on_cpu():
    """No 'model' mesh axis on CPU -> identical results to moe_apply."""
    from repro.models.config import MoEConfig
    from repro.models.moe import init_moe, moe_apply
    from repro.models.moe_a2a import moe_apply_a2a

    cfg = MoEConfig(num_experts=4, top_k=2, d_ff_expert=16,
                    capacity_factor=2.0)
    params = init_moe(jax.random.PRNGKey(0), 8, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 8))
    y1, a1, _ = moe_apply(params, x, cfg)
    y2, a2 = moe_apply_a2a(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5)


def test_sparse_xent_matches_dense_for_peaked_teacher():
    """When the teacher's mass is inside the top-k, the truncated wire
    format is (nearly) exact, and top-1 confidence is exact."""
    V, k = 50, 8
    t = jnp.zeros((4, V)).at[:, 3].set(10.0).at[:, 7].set(8.0)
    s = jax.random.normal(jax.random.PRNGKey(0), (4, V))
    dense_ce, dense_conf = _dense_xent_and_conf(s, t)
    vals, idx = jax.lax.top_k(t, k)
    packed = {"vals": vals, "idx": idx,
              "lse": jax.nn.logsumexp(t.astype(jnp.float32), -1)}
    sparse_ce, sparse_conf = _sparse_xent_and_conf(s, packed)
    np.testing.assert_allclose(np.asarray(sparse_conf),
                               np.asarray(dense_conf), rtol=1e-5)
    # the truncated tail (~0.3% teacher mass here) is the wire format's
    # documented approximation
    np.testing.assert_allclose(np.asarray(sparse_ce), np.asarray(dense_ce),
                               rtol=2e-2)


@pytest.mark.parametrize("exchange", ["full", "topk"])
def test_distributed_mhd_step_runs(exchange):
    """The pod-parallel MHD step on CPU (roll degrades to an in-memory
    swap): loss finite, params move, both wire formats."""
    from repro.configs import get_reduced
    from repro.models.zoo import build_bundle
    from repro.optim.optimizers import OptimizerConfig, make_optimizer

    cfg = dataclasses.replace(get_reduced("minitron-4b"), num_aux_heads=2)
    bundle = build_bundle(cfg)
    opt = make_optimizer(OptimizerConfig(init_lr=0.01, total_steps=5))
    mhd = MHDConfig(nu_emb=1.0, nu_aux=1.0, num_aux_heads=2)
    dist = DistributedMHDConfig(num_clients=2, exchange=exchange, topk=8)
    step = make_distributed_mhd_step(bundle, opt, mhd, dist)

    params = jax.vmap(lambda k: bundle.init(k))(
        jax.random.split(jax.random.PRNGKey(0), 2))
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    batch = {
        "private_tokens": jax.random.randint(
            jax.random.PRNGKey(1), (2, 2, 16), 0, cfg.vocab_size),
        "public_tokens": jax.random.randint(
            jax.random.PRNGKey(2), (2, 16), 0, cfg.vocab_size),
    }
    new_state, metrics = jax.jit(step)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    moved = sum(float(jnp.sum(jnp.abs(a - b))) for a, b in zip(
        jax.tree.leaves(state["params"]), jax.tree.leaves(new_state["params"])))
    assert moved > 0


def test_hlo_cost_fusion_slice_awareness():
    """A scan whose body slices a big stacked operand must not charge the
    full stack per iteration."""
    from repro.roofline.hlo_cost import analyze

    def f(stack, x):
        def body(c, w):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, stack)
        return out

    stack = jax.ShapeDtypeStruct((32, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = jax.jit(f).lower(stack, x).compile()
    cost = analyze(c.as_text())
    stack_bytes = 32 * 128 * 128 * 4
    # naive accounting charges the full stack per iteration (~32 x 2 MB plus
    # carries = 67+ MB); slice-aware accounting stays well under half that
    assert cost.bytes < 16 * stack_bytes, cost.bytes


def test_nested_remat_same_loss():
    """remat='nested' must not change the computed loss."""
    from repro.configs import get_reduced
    from repro.models.zoo import build_bundle

    cfg = get_reduced("qwen2.5-32b")
    cfg12 = dataclasses.replace(cfg, num_layers=12,
                                stages=cfg.stages[:1].__class__(
                                    [dataclasses.replace(cfg.stages[0],
                                                         repeats=12)]))
    bundle = build_bundle(dataclasses.replace(cfg12, remat="unit"))
    params = bundle.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                          cfg.vocab_size)}
    l1, _ = bundle.loss(params, batch)
    bundle2 = build_bundle(dataclasses.replace(cfg12, remat="nested"))
    l2, _ = bundle2.loss(params, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    g1 = jax.grad(lambda p: bundle.loss(p, batch)[0])(params)
    g2 = jax.grad(lambda p: bundle2.loss(p, batch)[0])(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-3, atol=1e-5)


# ---------------------------------------------------------------------------
# the fleet's dataset on the device (data/pipeline.DeviceData)
# ---------------------------------------------------------------------------

STEPS = 5  # a pool round every 2 steps: two publish rounds


def _run_fleet():
    """A 2-client top-k fleet run for `STEPS` steps with the tracer on.
    Every client's ``private_iter.next`` is wrapped as the benchmark's
    ``checked_steps`` wraps it, recording each private batch."""
    from test_comm import _make_trainer

    from repro.comm import CommConfig
    from repro.obs import tracer as trace

    tracer = trace.enable()
    try:
        tr = _make_trainer("prediction_topk", K=2, labels=4, steps=STEPS,
                           comm=CommConfig(topk=2))
        recorded = {c.client_id: [] for c in tr.clients}
        for c in tr.clients:
            def record(nxt=c.private_iter.next, i=c.client_id):
                b = nxt()
                recorded[i].append({k: np.array(v) for k, v in b.items()})
                return b

            c.private_iter.next = record
        metrics = [tr.step(t) for t in range(STEPS)]
    finally:
        trace.disable()
    for c in tr.clients:
        del c.private_iter.next
    return {"trainer": tr, "metrics": metrics, "recorded": recorded,
            "events": tracer.events()}


@pytest.fixture(scope="module")
def fleets():
    """The same fleet twice: its dataset on the device (the CPU reports no
    memory limit), and on the host because the fits check refuses."""
    resident = _run_fleet()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr("repro.core.runtime.fits_on_device", lambda nbytes: False)
        host = _run_fleet()
    return resident, host


def _where_data_lives(run):
    instant, = [e for e in run["events"] if e["name"] == "data/resident"]
    spans = [e for e in run["events"] if e["ph"] == "X" and
             e["name"] in ("data/private", "data/public", "data/publish")]
    return instant["args"], spans


def test_resident_dataset_trains_bitwise_as_the_host_path(fleets):
    resident, host = fleets
    tr = resident["trainer"]
    assert tr.public.resident and all(c.private_iter.resident
                                      for c in tr.clients)
    assert not host["trainer"].public.resident
    assert resident["metrics"] == host["metrics"]  # every loss, every step
    assert any(m[f"c{i}/distill_active"] for m in resident["metrics"]
               for i in (0, 1))  # the published windows were distilled


def test_data_location_is_in_the_trace(fleets):
    from repro.data.pipeline import dataset_nbytes

    for run, on_device in zip(fleets, (True, False)):
        args, spans = _where_data_lives(run)
        data = run["trainer"]._data
        assert args["on_device"] is on_device
        assert args["nbytes"] == (
            sum(v.nbytes for v in data.rows.values()) if on_device
            else dataset_nbytes(data))
        assert {e["name"] for e in spans} == {"data/private", "data/public",
                                              "data/publish"}
        assert all(e["args"]["resident"] is on_device for e in spans)
        # what crossed host→device: the rows' int32 indices on the device
        # path, the rows themselves (float32 8x8 RGB images) on the host
        bs = run["trainer"].run_cfg.batch_size
        private = [e["args"]["nbytes"] for e in spans
                   if e["name"] == "data/private"]
        assert set(private) == {bs * 4 if on_device else bs * (8 * 8 * 3 + 1)
                                * 4}


def test_recording_hook_sees_device_batches_equal_to_the_host_gather(fleets):
    resident, host = fleets
    for i in (0, 1):
        assert len(resident["recorded"][i]) == STEPS  # one per step
        for dev, ref in zip(resident["recorded"][i], host["recorded"][i]):
            assert set(dev) == set(ref) == {"images", "labels"}
            for k in dev:
                # the host batch as the jit boundary uploads it: int64
                # labels become int32 there, as on the device
                assert dev[k].dtype == jnp.asarray(ref[k]).dtype
                assert dev[k].tobytes() == ref[k].astype(dev[k].dtype) \
                    .tobytes()


def test_reinit_client_rebuilds_its_iterator_on_the_resident_data(fleets):
    resident, _ = fleets
    tr = resident["trainer"]
    tr.reinit_client(1)
    it = tr.clients[1].private_iter
    assert it.resident and it.arrays is tr._data
    first = it.next()  # the stream is rewound to its start
    assert isinstance(first["images"], jax.Array)
    for k, v in resident["recorded"][1][0].items():
        assert np.array(first[k]).tobytes() == v.tobytes()


@pytest.mark.parametrize("in_use", [0, 2**20])
def test_dataset_over_the_limit_stays_on_the_host(monkeypatch, in_use):
    """A device that reports 4 KiB more than it has in use: the dataset
    stays on the host, also where it is under a tenth of the device's
    limit but over a tenth of what is free."""
    from test_comm import _make_trainer

    import repro.data.pipeline as pipeline
    from repro.obs import tracer as trace

    monkeypatch.setattr(pipeline, "device_memory_stats", lambda: {
        "bytes_limit": in_use + 4096, "bytes_in_use": in_use})
    tracer = trace.enable()
    try:
        tr = _make_trainer("prediction_topk", K=2, labels=4)
    finally:
        trace.disable()
    instant, = [e for e in tracer.events() if e["name"] == "data/resident"]
    nbytes = instant["args"]["nbytes"]
    assert nbytes > pipeline.RESIDENT_SHARE * 4096
    if in_use:
        assert nbytes < pipeline.RESIDENT_SHARE * (in_use + 4096)
    assert instant["args"]["on_device"] is False
    assert not tr.public.resident
    assert not any(c.private_iter.resident for c in tr.clients)
    assert isinstance(tr.clients[0].private_iter.next()["images"],
                      np.ndarray)
