"""Every Pallas kernel compiles for a TPU v5e chip at main-path widths.

The TPU compiler is installed with jaxlib, so each kernel is lowered with
``interpret=False`` and compiled for the first device of a *described*
``v5e:2x2`` topology — no chip needed.  This catches what interpret mode
cannot: blocks that break the (8, 128) tiling rule, layouts Mosaic will not
verify, and too much VMEM.

Widths are those of the main path: c20d200k (192 items, W=6) with 4,096
candidates against 8,192 transaction rows; its vertical layout of 200,000
rows (6,250 words) with kmax=3; mushroom (119 items, W=4) rules R=2,048
against Q=256 baskets.  Wider catalogs are bounded by each kernel's VMEM
estimate (``kernels/tpu_params.py``): at the widest mask the estimate admits
for its default blocks the kernel must compile, one word wider the wrapper
must refuse it, and at the width of FIMI retail (16,470 items) the plans
leave every Pallas family out.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import pytest
from jax._src import cache_key
from jax._src import config as jax_config
from jax.sharding import SingleDeviceSharding

from repro.kernels.autotune import (DEFAULTS, PLAN_FAMILIES, block_vmem,
                                    plan_families)
from repro.kernels.delta_count import (delta_count_matmul_pallas,
                                       delta_count_pallas)
from repro.kernels.ops import _support_count_jnp
from repro.kernels.rule_match import (rule_scores_matmul_pallas,
                                      rule_scores_pallas)
from repro.kernels.support_count import (support_count_matmul_pallas,
                                         support_count_pallas)
from repro.kernels.tpu_params import VMEM_BUDGET_BYTES, VmemError
from repro.launch.cache import enable_compile_cache
from repro.kernels.vertical_count import (vertical_count_jnp,
                                          vertical_count_matmul_pallas,
                                          vertical_count_pallas)

C, T = 4096, 8192                 # c20d200k: 192 items, W=6
TW, KMAX = 6250, 3                # vertical layout of 200,000 rows
R, Q = 2048, 256                  # mushroom: 119 items, W=4
MAIN_W = {"support": 6, "delta": 6, "vertical": 6, "rule": 4}
RETAIL_W = -(-16_470 // 32)       # FIMI retail: 515 words

U32, I32, F32 = jnp.uint32, jnp.int32, jnp.float32

# kernel → (autotune family, call with interpret=False)
KERNELS = {
    "support_count_pallas": (
        "pallas", lambda c, t: support_count_pallas(c, t, interpret=False)),
    "support_count_matmul_pallas": (
        "matmul_pallas",
        lambda c, t: support_count_matmul_pallas(c, t, interpret=False)),
    "delta_count_pallas": (
        "delta_pallas",
        lambda c, t, s: delta_count_pallas(c, t, s, interpret=False)),
    "delta_count_matmul_pallas": (
        "delta_matmul_pallas",
        lambda c, t, s: delta_count_matmul_pallas(c, t, s, interpret=False)),
    "vertical_count_pallas": (
        "vertical_pallas",
        lambda v, i: vertical_count_pallas(v, i, interpret=False)),
    "vertical_count_matmul_pallas": (
        "vertical_matmul_pallas",
        lambda v, i: vertical_count_matmul_pallas(v, i, interpret=False)),
    "rule_scores_pallas": (
        "rules_pallas",
        lambda a, c, s, b: rule_scores_pallas(a, c, s, b, interpret=False)),
    "rule_scores_matmul_pallas": (
        "rules_matmul_pallas",
        lambda a, c, s, b: rule_scores_matmul_pallas(a, c, s, b,
                                                     interpret=False)),
}


def _arg_specs(name: str, w: int) -> list:
    """(shape, dtype) of each argument at mask width w; the vertical
    kernels get the 32·w + 1 item rows of a w-word catalog."""
    if name.startswith("support"):
        return [((C, w), U32), ((T, w), U32)]
    if name.startswith("delta"):
        return [((C, w), U32), ((T, w), U32), ((T,), I32)]
    if name.startswith("vertical"):
        return [((32 * w + 1, TW), U32), ((C, KMAX), I32)]
    return [((R, w), U32), ((R, w), U32), ((R,), F32), ((Q, w), U32)]


def _widest(family: str) -> int:
    """The widest mask whose default blocks fit the VMEM budget."""
    w = 1
    while block_vmem(family, DEFAULTS[family], w + 1) <= VMEM_BUDGET_BYTES:
        w += 1
    return w


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _lower(fn, specs, sharding):
    return jax.jit(fn).lower(*[jax.ShapeDtypeStruct(s, d, sharding=sharding)
                               for s, d in specs])


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    _, fn = KERNELS[name]
    w = MAIN_W[name.split("_")[0]]
    compiled = _lower(fn, _arg_specs(name, w), one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", list(KERNELS))
def test_widest_admitted_width_compiles_for_v5e(name, one_chip):
    """The VMEM estimate never admits a shape Mosaic refuses: at the widest
    mask it admits the kernel compiles, one word wider it is refused by the
    estimate before Mosaic sees it."""
    family, fn = KERNELS[name]
    w = _widest(family)
    compiled = _lower(fn, _arg_specs(name, w), one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
    with pytest.raises(VmemError, match="VMEM"):
        _lower(fn, _arg_specs(name, w + 1), one_chip)


def test_fimi_retail_width_plans_the_jnp_families(one_chip):
    """At 16,470 items no Pallas block config fits VMEM: every plan leaves
    the Pallas families out and says why, and the count families it keeps
    compile for the chip at that width."""
    for kind, families in PLAN_FAMILIES.items():
        kept, left_out = plan_families(kind, RETAIL_W, "tpu")
        pallas = [f for f in families if "pallas" in f]
        assert sorted(left_out) == sorted(pallas)
        assert all("VMEM" in why for why in left_out.values())
        assert kept == [f for f in families if "pallas" not in f]
        assert plan_families(kind, MAIN_W["support"], "tpu") == (
            list(families), {})
    _lower(_support_count_jnp, [((C, RETAIL_W), U32), ((T, RETAIL_W), U32)],
           one_chip).compile()
    _lower(vertical_count_jnp,
           [((16_471, TW), U32), ((C, KMAX), I32)], one_chip).compile()


def _cache_key_ir(lowered) -> str:
    """Hash of the IR that the persistent compilation cache keys on."""
    ir = cache_key._canonicalize_ir(lowered.compiler_ir("stablehlo"),
                                    cache_key.IgnoreCallbacks.NO)
    return hashlib.sha256(ir).hexdigest()


def test_kernel_cache_key_ignores_the_call_stack(one_chip, monkeypatch,
                                                 tmp_path):
    """Under the settings of enable_compile_cache, a serving program holding
    a Pallas kernel keys the same whether the kernel was first traced by an
    autotune-style call or by the program itself, and at any call depth, so
    a second process compiles it from the cache."""
    settings = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: settings.__setitem__(name, value))
    monkeypatch.delenv("JAX_INCLUDE_FULL_TRACEBACKS_IN_LOCATIONS",
                       raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    enable_compile_cache()
    full = settings["jax_include_full_tracebacks_in_locations"]
    specs = [((1024, 4), U32), ((1024, 4), U32), ((1024,), F32),
             ((32, 4), U32)]

    def serving_key(depth: int) -> str:
        if depth:
            return serving_key(depth - 1)

        def fn(a, c, s, b):
            return jax.lax.top_k(rule_scores_pallas(
                a, c, s, b, bq=256, br=512, interpret=False), 8)
        return _cache_key_ir(_lower(fn, specs, one_chip))

    keys = set()
    with jax_config.include_full_tracebacks_in_locations(full):
        for autotuned_first, depth in ((False, 0), (True, 0), (False, 3)):
            jax.clear_caches()
            if autotuned_first:
                _lower(lambda a, c, s, b: rule_scores_pallas(
                    a, c, s, b, bq=256, br=512, interpret=False),
                    specs, one_chip)
            keys.add(serving_key(depth))
    assert len(keys) == 1
