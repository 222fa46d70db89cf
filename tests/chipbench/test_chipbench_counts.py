"""The benchmark's counts and peaks against independent figures."""
import pytest

from chipbench import counts
from chipbench.peaks import peaks_for

# He et al. 2016, Table 1: ResNet-34 is 3.6e9 FLOPs, counted as
# multiply-adds, at 224 px with a 7x7/2 stem of 64 channels (118 M MACs
# at 112x112). The repo's 3x3/2 stem costs 3*3*3*64*112*112 = 21.7 M.
PUBLISHED_MACS = 3.6e9
STEM_7X7 = 7 * 7 * 3 * 64 * 112 * 112
STEM_3X3 = 3 * 3 * 3 * 64 * 112 * 112


def test_resnet34_forward_macs_match_the_published_count():
    macs = counts.resnet_forward_macs(224, (3, 4, 6, 3), 64, 1000, 0)
    expect = PUBLISHED_MACS - STEM_7X7 + STEM_3X3
    assert macs == pytest.approx(expect, rel=0.03)


def test_aux_heads_add_one_head_each():
    base = counts.resnet_forward_macs(224, (3, 4, 6, 3), 64, 1000, 0)
    aux = counts.resnet_forward_macs(224, (3, 4, 6, 3), 64, 1000, 4)
    assert aux - base == 4 * 512 * 1000


def test_fleet_step_flops_amortise_the_publish_window():
    f = counts.mhd_fleet_step_flops(10.0, clients=2, private_batch=3,
                                    public_batch=4, publish_window=5,
                                    publish_every=5)
    assert f == 2 * (3 * 10.0 * 7 + 10.0 * 4)


def test_fleet_step_flops_count_supervised_steps_on_the_private_batch():
    f = counts.mhd_fleet_step_flops(10.0, clients=2, private_batch=3,
                                    public_batch=4, publish_window=5,
                                    publish_every=5, distill_share=0.25)
    assert f == 2 * (3 * 10.0 * (3 + 0.25 * 4) + 10.0 * 4)


def test_topk_wire_counts_read_once_and_write_2k_plus_1():
    assert counts.topk_wire_bytes(10, 1000, 8) == 10 * 1000 * 4 + 10 * 17 * 4
    assert counts.topk_wire_ops(10, 1000) == 10 * 1000 * 5


def test_peaks_are_published_and_unknown_devices_raise():
    v5e = peaks_for("TPU v5 lite")
    assert v5e["flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v99")


def _program_init_shapes(workload):
    """Shapes of the program's own initial parameters for a cell's client
    (no arrays are made), and the benchmark's weights for the same."""
    import jax

    from chipbench import cell as C
    from repro.exp.runner import build_bundles

    cell = C.Cell(workload, 1)
    bundle = build_bundles(cell.spec())[0]
    prog = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    bench = jax.eval_shape(cell.family.weights_fn(cell.cfg),
                           jax.random.PRNGKey(0))
    return cell, prog, bench


@pytest.mark.parametrize("workload", [
    "resnet34_fleet8.distill", "mamba2_370m_fleet4.distill"])
def test_benchmark_weights_have_the_programs_layout(workload):
    import jax

    _, prog, bench = _program_init_shapes(workload)
    assert jax.tree.structure(prog) == jax.tree.structure(bench)
    for p, b in zip(jax.tree.leaves(prog), jax.tree.leaves(bench)):
        assert (p.shape, p.dtype) == (b.shape, b.dtype)


def test_mamba2_parameter_count_matches_the_programs_init():
    import jax

    cell, prog, _ = _program_init_shapes("mamba2_370m_fleet4.distill")
    n = sum(x.size for x in jax.tree.leaves(prog))
    assert cell.family.param_count(cell.cfg) == n
    # published 370m at 48 layers: 4 layers leave the embedding's 51.5 M,
    # 4 x 6.6 M of mixers and the two aux heads' 103 M
    assert n == 180_865_408


def test_mamba2_fleet_step_flops_match_a_hand_count():
    from chipbench import cell as C

    # per token: 4 layers x 7.1 M multiply-adds of mixer, then 3 heads x
    # 1024 x 50280 = 154 M; x 2 FLOPs x 1024 tokens = 375 GFLOP a sequence;
    # 4 clients x (3 x 2 trained sequences + 1 published) = 28 sequences
    cell = C.Cell("mamba2_370m_fleet4.distill", 1)
    assert cell.flops_per_fleet_step() == pytest.approx(28 * 375e9, rel=0.01)
