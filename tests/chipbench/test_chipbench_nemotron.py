"""The Nemotron-H configuration's weights, counts and grouped-matmul
counts against the program's own initialisation and hand counts."""
import pytest

WORKLOAD = "nemotron3_nano_fleet2.distill"


def _program_init_shapes():
    """Shapes of the program's own initial parameters for the cell's
    client (no arrays are made), and the benchmark's weights for the
    same."""
    import jax

    from chipbench import cell as C
    from repro.exp.runner import build_bundles

    cell = C.Cell(WORKLOAD, 1)
    bundle = build_bundles(cell.spec())[0]
    prog = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    bench = jax.eval_shape(cell.family.weights_fn(cell.cfg),
                           jax.random.PRNGKey(0))
    return cell, prog, bench


def test_nemotron_weights_have_the_programs_layout():
    import jax

    _, prog, bench = _program_init_shapes()
    assert jax.tree.structure(prog) == jax.tree.structure(bench)
    for p, b in zip(jax.tree.leaves(prog), jax.tree.leaves(bench)):
        assert (p.shape, p.dtype) == (b.shape, b.dtype)


def test_nemotron_parameter_count_matches_the_programs_init():
    import jax

    cell, prog, _ = _program_init_shapes()
    n = sum(x.size for x in jax.tree.leaves(prog))
    assert cell.family.param_count(cell.cfg) == n
    # 3 Mamba2 layers of 38.7 M, one attention layer of 23.4 M, 3 MoE
    # layers of 100.1 M (8 experts, the shared expert, the router), and
    # the embedding, head and one aux head of 44.0 M each
    assert n == pytest.approx(3 * 38.7e6 + 23.4e6 + 3 * 100.1e6
                              + 3 * 44.0e6, rel=1e-3)


def test_nemotron_fleet_step_flops_match_a_hand_count():
    from chipbench import cell as C

    # per token: 3 x 39.8 M (Mamba2) + 3 x 24.0 M (router, 6 x 8 / 128 of
    # an expert, the shared expert) + 31.8 M (attention, 1024.5 keys on
    # average) = 223.2 M multiply-adds, x 2048 tokens; heads over the 512
    # kept positions: 2 x 2688 x 16384 each; x 2 FLOPs = 1.005 TFLOP a
    # sequence; 2 clients x (3 x 2 trained sequences + 1 published)
    cell = C.Cell(WORKLOAD, 1)
    seq = 2 * (2048 * (3 * 39.78e6 + 3 * 24.04e6 + 31.79e6)
               + 512 * 2 * 2688 * 16384)
    assert cell.flops_per_fleet_step() == pytest.approx(14 * seq, rel=1e-3)


def test_moe_gmm_counts_per_call():
    from chipbench import moe_counts

    assert moe_counts.gmm_ops(768, 2688, 1856) == 2 * 768 * 2688 * 1856
    # 8 held matrices in bfloat16, and 768 rows in at 2 bytes and out at 4
    assert moe_counts.gmm_bytes(768, 8, 2688, 1856) == \
        8 * 2688 * 1856 * 2 + 768 * (2688 + 1856) * 3
