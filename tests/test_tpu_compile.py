"""Compile the main path's kernels at real widths for a DESCRIBED v5e.

The TPU compiler is installed in the sandbox and compiles for a chip
that is described, not attached: what it refuses here (a slice off the
tiling, too much fast memory, a program that does not fit the device)
costs no chip time.  A compile that passes is not a chip run and says
nothing about results or speed — chip_smoke.py is the run.

Code that asks ``jax.default_backend()`` still sees the CPU here, so the
kernels get ``interpret=False`` and the model functions an ``attn_fn``
that says so, explicitly.  The persistent compilation cache is off around
these tests: an entry written by such a compile cannot be read back
without a chip, and the next compile would warn about it.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from tensorflowonspark_tpu import ops  # noqa: E402
from tensorflowonspark_tpu.models import moe, transformer  # noqa: E402

# the bench transformer's width (bench.py _transformer_bench)
B, S, H, D = 8, 2048, 8, 128
LM = transformer.Config(vocab_size=16384, dim=H * D, n_layers=8, n_heads=H,
                        max_seq=S, dtype="bfloat16", attn_impl="flash")
FLASH = functools.partial(ops.flash_attention, causal=True, interpret=False)


@pytest.fixture(scope="module")
def v5e():
    """Sharding on one chip of a described v5e 2x2, cache off meanwhile."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler on this host
        pytest.skip(f"cannot describe a v5e here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _flash_grad(bwd_impl):
    def loss(q, k, v, g):
        out = FLASH(q, k, v, bwd_impl=bwd_impl)
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1, 2))


def _lm_params(sharding):
    shapes = jax.eval_shape(lambda k: transformer.init(k, LM),
                            jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        shapes)


def _programs(sh):
    """name -> (fn, abstract args, whether a pallas kernel must be in it)"""
    def a(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    qkv = a((B, S, H, D), jnp.bfloat16)
    slots, block = 32, 16
    per_slot = S // block
    pool = a((1 + 2 * slots * per_slot, LM.n_layers, H, block, D),
             jnp.bfloat16)
    # latent attention's prefill at the published widths: 64 heads, q/k
    # 192 wide against v 128, 8,192 positions (one head's K and V stay in
    # VMEM: the kernel has to ask for more than the default scoped limit)
    lat_qk = a((1, 8192, 64, 192), jnp.bfloat16)
    lat_v = a((1, 8192, 64, 128), jnp.bfloat16)
    # the expert layer at a decode step's size: 16 tokens x top-8 over 32
    # held experts of 128, width 4096 -> 2048 (grouped products)
    experts = {
        "router": a((4096, 128), jnp.bfloat16),
        "router_bias": a((128,), jnp.float32),
        "wg": a((32, 4096, 2048), jnp.bfloat16),
        "wu": a((32, 4096, 2048), jnp.bfloat16),
        "wd": a((32, 2048, 4096), jnp.bfloat16)}
    return {
        "flash_fwd_latent": (
            functools.partial(FLASH, scale=0.135), (lat_qk, lat_qk, lat_v),
            True),
        "expert_layer_decode": (
            lambda p, x: moe.apply(p, x, top_k=8, routed_scale=2.5)[0],
            (experts, a((16, 1, 4096), jnp.bfloat16)), True),
        "flash_fwd": (FLASH, (qkv, qkv, qkv), True),
        "flash_bwd_pallas": (_flash_grad("pallas"), (qkv,) * 4, True),
        "flash_bwd_xla": (_flash_grad("xla"), (qkv,) * 4, True),
        "fused_rmsnorm": (
            functools.partial(ops.fused_rmsnorm, interpret=False),
            (a((B * S, H * D), jnp.bfloat16), a((H * D,), jnp.float32)),
            True),
        "decode_step_paged": (
            lambda p, toks, pk, pv, tables, lens:
                transformer.decode_step_paged(p, toks, LM, pk, pv, tables,
                                              lens),
            (_lm_params(sh), a((slots, 1), jnp.int32), pool, pool,
             a((slots, per_slot), jnp.int32), a((slots,), jnp.int32)),
            False),  # attention there is an einsum, not a kernel
        "prefill": (
            lambda p, toks, lens: transformer.prefill(
                p, toks, LM, lengths=lens, attn_fn=FLASH),
            (_lm_params(sh), a((8, 1024), jnp.int32), a((8,), jnp.int32)),
            True),
    }


@pytest.mark.parametrize("name", [
    "flash_fwd", "flash_bwd_pallas", "flash_bwd_xla", "fused_rmsnorm",
    "decode_step_paged", "prefill", "flash_fwd_latent",
    "expert_layer_decode"])
def test_compiles_for_described_v5e(v5e, name):
    fn, args, has_kernel = _programs(v5e)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == has_kernel
    mem = compiled.memory_analysis()
    resident = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes)
    assert resident < 16 * 2 ** 30, f"{name} needs {resident} bytes"


@pytest.mark.parametrize("tree", ["float32", "resident"])
def test_step_of_the_resident_tree_converts_no_weight(v5e, tree):
    """What the serving engine holds (``DecodeFns.resident``: the matrices
    and the table in the compute type) leaves the compiled step's entry
    computation without a conversion; the float32 tree's converts the
    table and the four stacked matrices there, once a step (the
    control)."""
    import re

    fn, args, _ = _programs(v5e)["decode_step_paged"]
    params = args[0]
    if tree == "resident":
        held = jax.eval_shape(LM.decode_fns().resident, params)
        params = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e),
            held)
    text = jax.jit(fn).lower(params, *args[1:]).compile().as_text()
    entry = re.search(r"^ENTRY .*?^}", text, re.S | re.M).group(0)
    converted = re.findall(r"= (\S+?)\{[^ ]* convert\(", entry)
    if tree == "resident":
        assert converted == []
    else:
        assert len(converted) == 5 and all(
            c.startswith("bf16[") for c in converted), converted
