"""The Pallas kernels compile for a TPU v5e at the served models' widths.

Interpret mode (tests/test_kernels.py, tests/test_layout_native.py) checks
the kernels' arithmetic but not what Mosaic, the TPU kernel compiler,
accepts: block shapes, tiling, fast-memory use.  Here every kernel the
serving path dispatches on a TPU is compiled for a *described* v5e chip,
which needs the TPU compiler (libtpu) but no chip, at the widths of the
paper's tconst-41m and of smollm-360m.

The topology is described inside a fixture, never at import: only one
process at a time may load libtpu, and under pytest-xdist every worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import get_config
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_fwd_pallas
from repro.kernels.paged_decode_attention import paged_decode_attention_pallas

PAGE = 64
# arch -> (slots, KV length per slot: tconst's W_oh window, an LM cache)
WIDTHS = {"tconst_41m": (2, None), "smollm_360m": (4, 512)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                        # no libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _widths(arch):
    cfg = get_config(arch)
    B, S = WIDTHS[arch]
    return (B, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
            S or cfg.tconst.w_oh)


def _cases(arch, sd):
    """kernel name -> (fn, argument shapes) at ``arch``'s widths."""
    B, H, KV, D, S = _widths(arch)
    x = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sd)
    bf, f32, i8, i32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32
    pool = B * (S // PAGE) + 1                    # + the trash page
    q = x((B, H, D), bf)
    vl = x((B,), i32)
    cache = lambda dt: x((B, S, KV, D), dt)
    scale = x((B, S, KV, 1), f32)
    pages = lambda dt: x((pool, PAGE, KV, D), dt)
    pscale = x((pool, PAGE, KV, 1), f32)
    table = x((B, S // PAGE), i32)
    seq = lambda heads: x((1, S, heads, D), bf)
    pos = x((1, S), i32)
    return {
        "decode": (decode_attention_pallas,
                   (q, cache(bf), cache(bf), vl)),
        "decode_int8": (
            lambda q, k, v, vl, ks, vs: decode_attention_pallas(
                q, k, v, vl, k_scale=ks, v_scale=vs),
            (q, cache(i8), cache(i8), vl, scale, scale)),
        "paged": (paged_decode_attention_pallas,
                  (q, pages(bf), pages(bf), table, vl)),
        "paged_int8": (
            lambda q, pk, pv, pt, vl, ks, vs: paged_decode_attention_pallas(
                q, pk, pv, pt, vl, k_scale=ks, v_scale=vs),
            (q, pages(i8), pages(i8), table, vl, pscale, pscale)),
        "flash": (flash_attention_fwd_pallas,
                  (seq(H), seq(KV), seq(KV), pos, pos)),
    }


@pytest.mark.parametrize("kernel", ["decode", "decode_int8", "paged",
                                    "paged_int8", "flash"])
@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_kernel_compiles_for_v5e(one_chip, arch, kernel):
    fn, shapes = _cases(arch, one_chip)[kernel]
    hlo = jax.jit(fn).lower(*shapes).compile().as_text()
    assert hlo.count("tpu_custom_call") == 1, \
        f"{kernel} at {arch} widths compiled without its Mosaic kernel"


def test_ssd_state_update_compiles_for_v5e(one_chip):
    """granite-4.0-h-micro's Mamba-2 decode step: one Mosaic kernel, the
    donated state stack updated in place (aliased, no copy of it)."""
    from repro.kernels.ssd_state_update import ssd_state_update_pallas
    cfg = get_config("granite-4.0-h-micro")
    L, B, H, P, N = 9, 4, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    x = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=one_chip)
    f32 = jnp.float32
    stack = x((L, B, H, P, N), f32)
    compiled = jax.jit(ssd_state_update_pallas, donate_argnums=0).lower(
        stack, x((), jnp.int32), x((B, H), f32), x((B, H, P), f32),
        x((B, N), f32), x((B, N), f32), x((B,), bool)).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 1
    stack_bytes = L * B * H * P * N * 4
    assert compiled.memory_analysis().alias_size_in_bytes == stack_bytes
    assert f"f32[{L},{B},{H},{P},{N}]" not in "".join(
        line for line in hlo.splitlines() if " copy(" in line)
