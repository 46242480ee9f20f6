"""Roofline cost-model tests: ``collective_stats`` HLO parsing (the
measurement half of the predicted-vs-measured loop — exercised against both
synthetic HLO text and whatever the installed jax pin actually compiles) and
the ``select_moe_parallel`` collective cost model behind ``moe_parallel=
'auto'``."""

import jax
import jax.numpy as jnp
import pytest

from repro import roofline
from repro.configs import get_config
from repro.launch.mesh import make_debug_mesh, make_node_mesh
from repro.roofline import DCN_BW, ICI_BW_PER_LINK, axis_bandwidth

BASE = get_config("mixtral_8x7b").reduced().replace(
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    num_experts=8, top_k=2, moe_d_ff=198, vocab_size=128, sliding_window=16,
    attn_chunk=16, moe_a2a_capacity=1.0)


# -- collective_stats: HLO text parsing --------------------------------------


def test_collective_stats_basic_kinds():
    hlo = "\n".join([
        "%ar = f32[16,64]{1,0} all-reduce(%x), replica_groups={}",
        "%a2a = bf16[4,8,32]{2,1,0} all-to-all(%y), dimensions={0}",
        "%ag = f32[128]{0} all-gather(%z), dimensions={0}",
        "%add = f32[16,64]{1,0} add(%a, %b)",          # not a collective
    ])
    s = roofline.collective_stats(hlo)
    assert s["bytes"]["all-reduce"] == 16 * 64 * 4
    assert s["bytes"]["all-to-all"] == 4 * 8 * 32 * 2
    assert s["bytes"]["all-gather"] == 128 * 4
    assert s["counts"]["all-reduce"] == 1
    assert s["counts"]["all-to-all"] == 1
    assert s["total_bytes"] == 16 * 64 * 4 + 4 * 8 * 32 * 2 + 128 * 4
    assert s["total_count"] == 3


def test_collective_stats_tuple_result_and_root():
    # Tuple-shaped results (multi-operand all-reduce) sum every element;
    # ROOT-prefixed lines must parse like any other.
    hlo = "\n".join([
        "%ar = (f32[8,4], bf16[16]) all-reduce(%a, %b), to_apply=%sum",
        "ROOT %out = u32[2,2]{1,0} all-to-all(%c)",
    ])
    s = roofline.collective_stats(hlo)
    assert s["bytes"]["all-reduce"] == 8 * 4 * 4 + 16 * 2
    assert s["bytes"]["all-to-all"] == 2 * 2 * 4
    assert s["counts"]["all-to-all"] == 1


def test_collective_stats_ignores_operand_shapes():
    # Operands are %refs without shapes in compiled HLO; a line mentioning a
    # collective by name inside a comment/metadata must not count.
    hlo = "%c = f32[4]{0} add(%a, %b), metadata={op_name=\"all-reduce\"}"
    s = roofline.collective_stats(hlo)
    assert s["total_bytes"] == 0
    assert s["total_count"] == 0


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 host devices")
def test_collective_stats_parses_compiled_hlo_this_pin():
    """The regex must keep matching whatever HLO text the *installed* jax
    pin emits (CI runs this on both pins): compile a psum and an all_to_all
    under shard_map and assert their bytes are extracted."""
    mesh = make_debug_mesh(1, 8)

    def body(x):
        # x is the local (8, 16) shard here
        y = jax.lax.psum(x, "model")
        z = jax.lax.all_to_all(x, "model", 0, 0)
        return y, z

    x = jnp.zeros((8 * 8, 16), jnp.float32)
    from jax.sharding import PartitionSpec as P
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("model"),),
                              out_specs=(P("model"), P("model")),
                              check_vma=False))
    hlo = f.lower(x).compile().as_text()
    s = roofline.collective_stats(hlo)
    assert s["counts"]["all-reduce"] >= 1, hlo[:2000]
    assert s["bytes"]["all-reduce"] > 0
    assert s["counts"]["all-to-all"] >= 1
    assert s["bytes"]["all-to-all"] > 0


# -- analytic collective costs ----------------------------------------------


def test_psum_cost_ring_formula_and_bandwidth_tiers():
    L, d, it = 128, 64, 4
    b_model, t_model = roofline._psum_cost(L, d, it, (("model", 4),))
    assert b_model == int(2 * 3 / 4 * L * d * it)
    assert t_model == pytest.approx(b_model / ICI_BW_PER_LINK)
    # the 'node' axis crosses the data-center network: same bytes on a
    # same-size axis, strictly more seconds
    b_node, t_node = roofline._psum_cost(L, d, it, (("node", 4),))
    assert b_node == b_model
    assert t_node == pytest.approx(b_node / DCN_BW)
    assert t_node > t_model
    assert axis_bandwidth("node") == DCN_BW
    assert axis_bandwidth("model") == ICI_BW_PER_LINK
    # 1-way axes are free
    assert roofline._psum_cost(L, d, it, (("model", 1),)) == (0, 0.0)


def test_a2a_hop_cost():
    rows, n, d, it = 256, 4, 64, 2
    b, t = roofline._a2a_hop_cost(rows, n, d, it, "model")
    assert b == int(2 * rows * 3 / 4 * d * it)
    assert t == pytest.approx(b / ICI_BW_PER_LINK)
    assert roofline._a2a_hop_cost(rows, 1, d, it, "model") == (0, 0.0)


# -- select_moe_parallel: the auto optimizer ---------------------------------


def _modes(decision):
    return {c.mode: c for c in decision.table}


def test_auto_picks_ep_a2a_where_predicted_faster():
    # h ~ 3d with a tight capacity: the exchange's memory savings beat its
    # wire cost outright (the parallel/* bench family measures this same
    # config).
    mesh = make_debug_mesh(2, 4)
    d = roofline.select_moe_parallel(BASE, mesh, 1024)
    assert d.mode == "ep_a2a"
    assert d.source == "auto"
    row = _modes(d)
    assert row["ep_a2a"].chosen and not row["ep"].chosen
    assert row["ep_a2a"].t_total_s < row["ep"].t_total_s
    assert row["ep_a2a"].a2a_bytes > 0
    assert row["ep"].a2a_bytes == 0
    # tp is out of the ranking: 198 % 4 != 0
    assert not row["tp"].feasible


def test_auto_picks_ep_where_exchange_does_not_pay():
    # h ~ d at capacity 2: the doubled exchange buffers erase the memory
    # win and the wire cost stands alone — replicated EP is predicted
    # faster.
    cfg = BASE.replace(moe_d_ff=66, moe_a2a_capacity=2.0)
    mesh = make_debug_mesh(2, 4)
    d = roofline.select_moe_parallel(cfg, mesh, 1024)
    assert d.mode == "ep"
    row = _modes(d)
    assert row["ep"].t_total_s < row["ep_a2a"].t_total_s


def test_auto_falls_back_to_tp_on_awkward_expert_count():
    cfg = BASE.replace(num_experts=6, moe_d_ff=64)
    d = roofline.select_moe_parallel(cfg, make_debug_mesh(2, 4), 1024)
    assert d.mode == "tp"
    row = _modes(d)
    assert not row["ep"].feasible and "divisible" in row["ep"].why


def test_auto_live_bytes_tiebreak_within_slack():
    # A shape where ep and ep_a2a are within the time slack but the
    # exchange's live set is materially (> 8 MiB) smaller: memory wall
    # breaks the tie.
    cfg = BASE.replace(d_model=128, moe_d_ff=390, moe_a2a_capacity=2.0)
    mesh = make_debug_mesh(2, 4)
    d = roofline.select_moe_parallel(cfg, mesh, 2048)
    row = _modes(d)
    assert row["ep_a2a"].t_total_s <= row["ep"].t_total_s * \
        (1.0 + roofline.AUTO_TIME_SLACK)
    assert row["ep"].live_bytes - row["ep_a2a"].live_bytes \
        > roofline.AUTO_LIVE_EPS
    assert d.mode == "ep_a2a"


def test_auto_prefers_ep_on_tiny_slabs():
    # Decode/test-sized slabs: every mode is within slack and within the
    # live-bytes epsilon — the earliest ep-like mode in MOE_MODE_ORDER wins
    # unless tp is predicted faster outright.
    cfg = BASE.replace(moe_d_ff=66, moe_a2a_capacity=2.0)
    d = roofline.select_moe_parallel(cfg, make_debug_mesh(2, 4), 32)
    assert d.mode == "ep"


def test_hier_selected_on_node_mesh():
    # On a ('data','node','model') mesh with h % n_model != 0 (tp out) and
    # h ~ 6d, the two-hop exchange is predicted faster than replicated EP
    # despite its DCN hop.
    cfg = BASE.replace(moe_d_ff=389)
    mesh = make_node_mesh(2, 2, 2)
    d = roofline.select_moe_parallel(cfg, mesh, 1024)
    row = _modes(d)
    assert not row["ep_a2a"].feasible          # flat a2a refuses node meshes
    assert row["ep_a2a_hier"].feasible
    assert d.mode == "ep_a2a_hier"


def test_forced_mode_keeps_table_provenance():
    cfg = BASE.replace(moe_parallel="ep")
    d = roofline.select_moe_parallel(cfg, make_debug_mesh(2, 4), 1024)
    assert d.mode == "ep" and d.source == "config"
    row = _modes(d)
    assert row["ep"].chosen
    # JSON-ready decision table rows for the dryrun record
    rows = d.table_rows()
    assert all(isinstance(r, dict) and "t_total_s" in r and "chosen" in r
               for r in rows)
    assert sum(r["chosen"] for r in rows) == 1


def test_no_mesh_resolves_single():
    d = roofline.select_moe_parallel(BASE, None, 1024)
    assert d.mode == "single" and d.source == "single"
    assert d.table == ()


def test_chunked_model_never_slower_than_unchunked():
    mesh = make_debug_mesh(2, 4)
    for L in (256, 1024, 4096):
        un = _modes(roofline.select_moe_parallel(BASE, mesh, L))["ep_a2a"]
        ch = _modes(roofline.select_moe_parallel(
            BASE.replace(moe_a2a_chunks=4), mesh, L))["ep_a2a"]
        assert ch.t_total_s <= un.t_total_s + 1e-12


def test_hardware_peaks_keyed_by_device_kind():
    from repro import hardware
    v5e = hardware.peaks("TPU v5 lite")
    assert v5e.bf16_flops == 197e12 and v5e.hbm_bw == 819e9
    assert v5e.source
    assert roofline.PEAK_FLOPS_BF16 == v5e.bf16_flops
    assert ICI_BW_PER_LINK == v5e.ici_bw / v5e.ici_links
    with pytest.raises(KeyError, match="no hardware peaks"):
        hardware.peaks("TPU v9 imaginary")
