"""Kernel impl families vs pure-jnp oracles: shape/dtype sweeps + properties.

Every kernel family (DESIGN.md §10) is checked bit-exact against its popcount
oracle: the bit-plane int8 matmul twins (``matmul``/``matmul_pallas``) must
agree with the ``jnp``/``pallas`` forms on ragged tails, W>1, empty candidates
and zero padding.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.core.bitset import (jpack_bits, junpack_bits, pack_itemsets,
                               vertical_pack)
from repro.kernels import support_count, support_count_ref
from repro.kernels.delta_count import (delta_count_jnp, delta_count_matmul,
                                       delta_count_matmul_pallas)
from repro.kernels.rule_match import (rule_scores_jnp, rule_scores_matmul,
                                      rule_scores_matmul_pallas)
from repro.kernels.support_count import (support_count_matmul,
                                         support_count_pallas)
from repro.kernels.vertical_count import (vertical_count_jnp,
                                          vertical_count_matmul,
                                          vertical_count_matmul_pallas)


@pytest.mark.parametrize("C,T,W", [
    (1, 1, 1), (3, 5, 1), (17, 33, 2), (64, 128, 3),
    (256, 512, 6), (300, 700, 8), (256, 512, 1),
])
def test_pallas_matches_ref_shapes(C, T, W):
    rng = np.random.default_rng(C * 1000 + T + W)
    cands = rng.integers(0, 2**32, (C, W), dtype=np.uint32)
    txns = rng.integers(0, 2**32, (T, W), dtype=np.uint32)
    ref = np.asarray(support_count_ref(jnp.asarray(cands), jnp.asarray(txns)))
    pal = np.asarray(support_count(cands, txns, impl="pallas_interpret"))
    jn = np.asarray(support_count(cands, txns, impl="jnp"))
    np.testing.assert_array_equal(pal, ref)
    np.testing.assert_array_equal(jn, ref)


@pytest.mark.parametrize("bc,bt", [(8, 16), (128, 256), (256, 512)])
def test_pallas_block_shapes(bc, bt):
    rng = np.random.default_rng(bc + bt)
    C, T, W = bc * 2, bt * 3, 4
    cands = rng.integers(0, 2**32, (C, W), dtype=np.uint32)
    txns = rng.integers(0, 2**32, (T, W), dtype=np.uint32)
    ref = np.asarray(support_count_ref(jnp.asarray(cands), jnp.asarray(txns)))
    pal = np.asarray(support_count_pallas(
        jnp.asarray(cands), jnp.asarray(txns), bc=bc, bt=bt, interpret=True))
    np.testing.assert_array_equal(pal, ref)


@given(st.lists(st.lists(st.integers(0, 60), min_size=0, max_size=10)
                .map(lambda x: sorted(set(x))), min_size=1, max_size=20),
       st.lists(st.lists(st.integers(0, 60), min_size=0, max_size=20)
                .map(lambda x: sorted(set(x))), min_size=1, max_size=30))
@settings(max_examples=25, deadline=None)
def test_support_count_is_subset_count(cand_sets, txn_sets):
    """Property: count == #transactions containing the candidate."""
    cands = pack_itemsets(cand_sets, 61)
    txns = pack_itemsets(txn_sets, 61)
    got = np.asarray(support_count(cands, txns, impl="pallas_interpret"))
    for i, cs in enumerate(cand_sets):
        want = sum(1 for t in txn_sets if set(cs) <= set(t))
        assert got[i] == want


def test_zero_padding_safety():
    """Zero txn rows never match non-empty candidates; zero candidates match all."""
    cands = pack_itemsets([[0], []], 32)
    txns = np.concatenate([pack_itemsets([[0], [1]], 32),
                           np.zeros((5, 1), np.uint32)])
    for impl in ("pallas_interpret", "matmul", "matmul_pallas_interpret"):
        got = np.asarray(support_count(cands, txns, impl=impl))
        assert got[0] == 1      # [0] ⊆ only the first txn
        assert got[1] == 7      # empty set ⊆ everything incl. zero rows


# ---------------------------------------------------------------------------
# bit-plane helpers and the matmul twins (DESIGN.md §10)
# ---------------------------------------------------------------------------

def test_bitplane_pack_roundtrip():
    rng = np.random.default_rng(0)
    words = rng.integers(0, 2**32, (13, 3), dtype=np.uint32)
    planes = junpack_bits(jnp.asarray(words))
    assert planes.shape == (13, 96) and planes.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(jpack_bits(planes)), words)
    # little bit-order: column w*32+b holds bit b of word w
    one = np.zeros((1, 2), np.uint32)
    one[0, 1] = 1 << 7
    col = np.asarray(junpack_bits(jnp.asarray(one)))[0]
    assert col[32 + 7] == 1 and col.sum() == 1


@pytest.mark.parametrize("C,T,W", [(1, 1, 1), (17, 33, 2), (300, 700, 8)])
@pytest.mark.parametrize("impl", [
    "matmul", pytest.param("matmul_pallas_interpret", id="matmul_pallas")])
def test_matmul_impls_match_ref(C, T, W, impl):
    rng = np.random.default_rng(C + T + W)
    cands = rng.integers(0, 2**32, (C, W), dtype=np.uint32)
    cands[0] = 0                     # empty candidate: matches everything
    txns = rng.integers(0, 2**32, (T, W), dtype=np.uint32)
    ref = np.asarray(support_count_ref(jnp.asarray(cands), jnp.asarray(txns)))
    got = np.asarray(support_count(cands, txns, impl=impl))
    np.testing.assert_array_equal(got, ref)


def test_support_count_matmul_blocking_invariance():
    rng = np.random.default_rng(3)
    cands = rng.integers(0, 2**32, (37, 2), dtype=np.uint32)
    txns = rng.integers(0, 2**32, (101, 2), dtype=np.uint32)
    ref = support_count_matmul(jnp.asarray(cands), jnp.asarray(txns),
                               block=101)
    for blk in (1, 7, 64, 4096):
        got = support_count_matmul(jnp.asarray(cands), jnp.asarray(txns),
                                   block=blk)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def _random_vertical(rng, n_items=37, n=101, kmax=5, C=23):
    db = pack_itemsets(
        [sorted(rng.choice(n_items, rng.integers(0, 8), replace=False))
         for _ in range(n)], n_items)
    vdb = vertical_pack(db, n_items)
    idx = np.full((C, kmax), n_items, np.int32)
    for i in range(C):
        k = rng.integers(0, kmax + 1)
        idx[i, :k] = rng.choice(n_items, k, replace=False)
    idx[C // 2, :] = n_items         # all-padding candidate (empty set)
    return vdb, idx


def test_vertical_matmul_matches_oracle():
    rng = np.random.default_rng(11)
    vdb, idx = _random_vertical(rng)
    ref = np.asarray(vertical_count_jnp(jnp.asarray(vdb), jnp.asarray(idx)))
    mm = np.asarray(vertical_count_matmul(jnp.asarray(vdb), jnp.asarray(idx),
                                          block=8))
    np.testing.assert_array_equal(mm, ref)
    mp = np.asarray(vertical_count_matmul_pallas(
        jnp.asarray(vdb), jnp.asarray(idx), bc=8, bt=64, interpret=True))
    np.testing.assert_array_equal(mp, ref)


def test_vertical_matmul_duplicate_slots():
    """Repeated item ids in a candidate row must stay AND-idempotent."""
    rng = np.random.default_rng(12)
    vdb, idx = _random_vertical(rng)
    idx[1, 1] = idx[1, 0]
    ref = np.asarray(vertical_count_jnp(jnp.asarray(vdb), jnp.asarray(idx)))
    mm = np.asarray(vertical_count_matmul(jnp.asarray(vdb), jnp.asarray(idx)))
    np.testing.assert_array_equal(mm, ref)


def test_delta_matmul_matches_oracle():
    rng = np.random.default_rng(21)
    C, T, W = 19, 26, 2
    cands = rng.integers(0, 2**32, (C, W), dtype=np.uint32)
    cands[0] = 0
    slab = rng.integers(0, 2**32, (T, W), dtype=np.uint32)
    slab[4] = 0
    signs = rng.choice(np.array([-1, 0, 1], np.int32), T)
    ref = np.asarray(delta_count_jnp(jnp.asarray(cands), jnp.asarray(slab),
                                     jnp.asarray(signs)))
    mm = np.asarray(delta_count_matmul(jnp.asarray(cands), jnp.asarray(slab),
                                       jnp.asarray(signs), block=8))
    np.testing.assert_array_equal(mm, ref)
    # pallas twin on pre-padded operands (sign-0 padding is a no-op)
    Cp, Tp = 24, 32
    cp = np.concatenate([cands, np.zeros((Cp - C, W), np.uint32)])
    sp = np.concatenate([slab, np.zeros((Tp - T, W), np.uint32)])
    sg = np.concatenate([signs, np.zeros(Tp - T, np.int32)])
    mp = np.asarray(delta_count_matmul_pallas(
        jnp.asarray(cp), jnp.asarray(sp), jnp.asarray(sg),
        bc=8, bt=16, interpret=True))[:C]
    np.testing.assert_array_equal(mp, ref)


@pytest.mark.parametrize("exclude_contained", [True, False])
def test_rule_scores_matmul_matches_oracle(exclude_contained):
    rng = np.random.default_rng(31)
    R, Q, W = 21, 14, 2
    antes = rng.integers(0, 2**32, (R, W), dtype=np.uint32)
    cons = rng.integers(0, 2**32, (R, W), dtype=np.uint32) & ~antes
    antes[2] = 0                     # empty antecedent: fires on every basket
    cons[3] = 0                      # empty consequent: contained everywhere
    scores = rng.random(R).astype(np.float32)
    baskets = rng.integers(0, 2**32, (Q, W), dtype=np.uint32)
    baskets[0] = 0xFFFFFFFF
    args = (jnp.asarray(antes), jnp.asarray(cons), jnp.asarray(scores),
            jnp.asarray(baskets))
    ref = np.asarray(rule_scores_jnp(*args, q_block=4,
                                     exclude_contained=exclude_contained))
    mm = np.asarray(rule_scores_matmul(*args, q_block=4,
                                       exclude_contained=exclude_contained))
    np.testing.assert_array_equal(mm, ref)
    mp = np.asarray(rule_scores_matmul_pallas(
        *args, bq=8, br=16, exclude_contained=exclude_contained,
        interpret=True))
    np.testing.assert_array_equal(mp, ref)


@given(st.lists(st.lists(st.integers(0, 60), min_size=0, max_size=10)
                .map(lambda x: sorted(set(x))), min_size=1, max_size=20),
       st.lists(st.lists(st.integers(0, 60), min_size=0, max_size=20)
                .map(lambda x: sorted(set(x))), min_size=1, max_size=30))
@settings(max_examples=25, deadline=None)
def test_matmul_support_count_is_subset_count(cand_sets, txn_sets):
    """Property: the matmul arm is an exact subset counter too."""
    cands = pack_itemsets(cand_sets, 61)
    txns = pack_itemsets(txn_sets, 61)
    got = np.asarray(support_count(cands, txns, impl="matmul"))
    gotp = np.asarray(support_count(cands, txns,
                                    impl="matmul_pallas_interpret"))
    for i, cs in enumerate(cand_sets):
        want = sum(1 for t in txn_sets if set(cs) <= set(t))
        assert got[i] == want
        assert gotp[i] == want


# ---------------------------------------------------------------------------
# no fallback that hides the device: plain Pallas names compile for the TPU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["pallas", "matmul_pallas"])
def test_compiled_pallas_off_tpu_raises(impl):
    cands = pack_itemsets([[0], [1, 2]], 32)
    txns = pack_itemsets([[0, 1, 2], [1]], 32)
    with pytest.raises(ValueError, match="_interpret"):
        support_count(cands, txns, impl=impl)


@pytest.mark.parametrize("impl", ["pallas", "matmul_pallas"])
def test_compiled_pallas_delta_and_rules_off_tpu_raise(impl):
    from repro.core import generate_ruleset, mine
    from repro.kernels.delta_count import delta_count
    from repro.serving.rules_engine import RuleServeEngine
    cands = pack_itemsets([[0], [1, 2]], 32)
    added = pack_itemsets([[0, 1, 2]], 32)
    with pytest.raises(ValueError, match="_interpret"):
        delta_count(cands, added, added[:0], impl=impl, autotune=False)
    res = mine([[0, 1], [0, 1], [0, 1, 2]], n_items=3, min_sup=0.5)
    rules = generate_ruleset(res, 0.5)
    assert len(rules)
    engine = RuleServeEngine(rules, impl=impl, autotune=False)
    with pytest.raises(ValueError, match="_interpret"):
        engine.query([[0]])


def test_count_kernel_roofline_needs_published_peaks():
    from repro.roofline import COUNT_PEAKS, count_kernel_roofline
    with pytest.raises(ValueError, match="no published peaks"):
        count_kernel_roofline("matmul", C=256, T=1024, W=6, seconds=1e-3,
                              device_kind="cpu")
    v5e = COUNT_PEAKS["TPU v5 lite"]
    assert (v5e["bf16_flops"], v5e["int8_ops"], v5e["mem_bw"]) == \
        (197e12, 393e12, 819e9)
    r = count_kernel_roofline("matmul", C=256, T=1024, W=6, seconds=1e-3,
                              device_kind="TPU v5 lite")
    assert r["bound"] == "compute" and r["peak"] == 393e12
    assert r["peak_frac"] == pytest.approx(2 * 256 * 1024 * 6 * 32 / 1e-3
                                           / 393e12)


def test_count_kernel_roofline_prices_each_impl():
    """Each impl is priced by the work its kernel cannot avoid; a job on n
    chips is compared against n chips' peak."""
    from repro.roofline import count_kernel_roofline
    kw = dict(C=4096, T=200_000, W=6, kmax=3, n_items=192, seconds=1.0,
              device_kind="TPU v5 lite")
    tw = 6250
    vp = count_kernel_roofline("vertical_pallas", **kw)
    # 16 candidate blocks of 256, each streaming all 193 item rows
    assert vp["achieved"] == 4.0 * (16 * 193 * tw + 4096 * 3 + 4096)
    # 8,124 rows are 254 words: one word block, read once
    small = count_kernel_roofline("vertical_pallas", **{**kw, "T": 8124})
    assert small["achieved"] == 4.0 * (193 * 254 + 4096 * 3 + 4096)
    assert count_kernel_roofline("vertical", **kw)["achieved"] == \
        4.0 * (4096 * 3 * tw + 4096)
    for impl in ("jnp", "pallas"):
        assert count_kernel_roofline(impl, **kw)["achieved"] == \
            4.0 * (6 * (4096 + 200_000) + 4096)
    assert count_kernel_roofline("vertical_matmul_pallas", **kw)[
        "achieved"] == 2.0 * 4096 * 200_000 * 192
    four = count_kernel_roofline("vertical_pallas", chips=4, **kw)
    assert four["peak"] == 4 * vp["peak"]
    assert four["peak_frac"] == pytest.approx(vp["peak_frac"] / 4)
    with pytest.raises(ValueError, match="no roofline model"):
        count_kernel_roofline("vertical_interpret", **kw)
