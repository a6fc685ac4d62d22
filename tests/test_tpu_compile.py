"""Every Pallas kernel compiles for a described TPU v5e at real widths.

Interpret mode (the rest of the suite) runs the kernel bodies as plain
XLA, so it cannot see what the chip's compiler refuses: blocks that break
the (8, 128) tiling rule, more VMEM than a kernel may use, primitives
Mosaic cannot lower.  Here the TPU compiler compiles each kernel for a
v5e chip that is described, not attached; nothing runs.  The topology is
described inside a fixture (never at import), so under several pytest
workers only the worker given this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import dequant_fold, flash_attention, mamba_scan
from repro.kernels import masked_agg, robust_agg, similarity

V5E_HBM_BYTES = 16 * 1024 ** 3

# fl-llm-100m (benchmarks/model_fl_bench.FULL_MODEL): one streaming block
# of client_chunk=1 rows over the flat decoder
DECODER_D = 100_369_280


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    described-chip executable is written there but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def vgg11_d():
    from repro.fl.small_models import vgg11
    shapes = jax.eval_shape(vgg11().init, jax.random.PRNGKey(0))
    return sum(s.size for s in jax.tree.leaves(shapes))


def _fold(n, d):
    def fn(u, w, acc):
        return masked_agg.masked_agg_update_kernel(u, w, acc)
    return fn, [((n, d), jnp.float32), ((n,), jnp.float32),
                ((d,), jnp.float32)]


def _flash(b, h, k, s, dh, window=None):
    def fn(q, kk, v):
        return flash_attention.flash_attention_kernel(q, kk, v,
                                                      window=window)
    return fn, [((b, h, s, dh), jnp.bfloat16), ((b, k, s, dh), jnp.bfloat16),
                ((b, k, s, dh), jnp.bfloat16)]


# name -> make(vgg11_d) -> (fn, [(shape, dtype), ...])
CASES = {
    # dense DiverseFL Step 4 over the paper's N=23 VGG-11 federation
    "similarity_vgg11_n23": lambda dv: (
        similarity.similarity_kernel,
        [((23, dv), jnp.float32)] * 2),
    # streaming Step 4 on one client block of the decoder
    "similarity_decoder_n1": lambda dv: (
        similarity.similarity_kernel,
        [((1, DECODER_D), jnp.float32)] * 2),
    "masked_agg_vgg11_n23": lambda dv: (
        masked_agg.masked_agg_kernel,
        [((23, dv), jnp.float32), ((23,), jnp.bool_)]),
    "masked_agg_update_decoder_n1": lambda dv: _fold(1, DECODER_D),
    # a cross-device block: the client axis is tiled, VMEM stays bounded
    "masked_agg_update_n1024": lambda dv: _fold(1024, (1 << 20) + 3),
    "dequant_fold_n1024": lambda dv: (
        lambda q, s, w, acc: dequant_fold.dequant_fold_update_kernel(
            q, s, w, acc, qblock=128),
        [((1024, (1 << 20) + 3), jnp.int8),
         ((1024, -(-((1 << 20) + 3) // 128)), jnp.float32),
         ((1024,), jnp.float32), (((1 << 20) + 3,), jnp.float32)]),
    "robust_agg_vgg11_n23": lambda dv: (
        lambda u: robust_agg.robust_agg_kernel(u, 5),
        [((23, dv), jnp.float32)]),
    # falcon-mamba-7b: d_inner 8192, d_state 16
    "mamba_scan_falcon_mamba_7b": lambda dv: (
        mamba_scan.mamba_scan_kernel,
        [((1, 2048, 8192, 16), jnp.float32)] * 2
        + [((1, 2048, 16), jnp.float32)]),
    # the kernel serves sequences past attn_direct_max (2048)
    "flash_attention_gemma_2b": lambda dv: _flash(1, 8, 1, 4096, 256),
    "flash_attention_minitron_8b": lambda dv: _flash(1, 32, 8, 4096, 128),
    "flash_attention_h2o_danube_swa": lambda dv: _flash(1, 32, 8, 4096, 80,
                                                        window=4096),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, vgg11_d):
    fn, specs = CASES[name](vgg11_d)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert peak <= V5E_HBM_BYTES, (name, peak)
