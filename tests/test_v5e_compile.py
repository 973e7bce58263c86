"""Compile-for-v5e checks: the kernels and the training step at real widths
are compiled by the TPU compiler for a described (not attached) v5e chip.

Nothing runs, so these say nothing about results or times; they catch what
interpret mode and the CPU backend cannot: block shapes off the (8, 128)
tiling, Mosaic lowering gaps, and programs that do not fit the chip's HBM.
The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import resolve_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.launch.mesh import make_mesh
from repro.optim import AdamW, warmup_cosine
from repro.pipeline import runtime as RT
from repro.pipeline import stage as ST

# HBM the TPU compiler lets one v5e program use (its "Ran out of memory in
# memory space hbm ... of 15.75G" limit; 16 GiB less the runtime's reserve)
V5E_HBM_BYTES = int(15.75 * 2 ** 30)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("T,S,causal", [(4096, 4096, True),
                                        (1, 4096, False)],
                         ids=["prefill-causal", "decode"])
def test_flash_attention_compiles_for_v5e(one_chip, T, S, causal):
    """GQA-width heads (batch*heads 16), head_dim 128, bf16."""
    def f(q, k, v):
        return flash_attention(q, k, v, scale=128 ** -0.5, causal=causal)
    args = [_shape((16, n, 128), jnp.bfloat16, one_chip) for n in (T, S, S)]
    hlo = jax.jit(f).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_ssd_scan_compiles_for_v5e_at_mamba2_widths(one_chip):
    """mamba2-2.7b: 80 heads x head_dim 64, d_state 128, chunk 256."""
    cfg = resolve_config("mamba2-2.7b")
    H, Pd, N = cfg.ssm.n_heads(cfg.d_model), cfg.ssm.head_dim, cfg.ssm.d_state
    assert (H, Pd, N, cfg.ssm.chunk) == (80, 64, 128, 256)
    B, T = 1, 4096
    args = [_shape((B, T, H, Pd), jnp.bfloat16, one_chip),
            _shape((B, T, H), jnp.float32, one_chip),
            _shape((H,), jnp.float32, one_chip),
            _shape((B, T, N), jnp.bfloat16, one_chip),
            _shape((B, T, N), jnp.bfloat16, one_chip)]
    compiled = jax.jit(
        lambda *a: ssd_scan(*a, chunk=cfg.ssm.chunk)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen3_train_step_fits_one_v5e(topo):
    """A 2-layer Qwen3-1.7B train step at its published widths (stages 1,
    AdamW, batch 8 x 1024 tokens, 8 micro-batches) compiles for one chip
    and its arguments plus temporaries fit the chip's HBM."""
    cfg = dataclasses.replace(resolve_config("qwen3-1.7b", layers=2),
                              stages=1, tensor=1)
    assert (cfg.d_model, cfg.d_ff, cfg.vocab) == (2048, 6144, 151936)
    mesh = make_mesh((1, 1, 1), ("data", "stage", "tensor"),
                     devices=topo.devices[:1])
    plan = ST.plan_stages(cfg)
    opt = AdamW(lr=warmup_cosine(3e-3, 20, 100), weight_decay=0.01)
    step, specs = RT.make_train_step(
        cfg, mesh, plan, RT.PipelineConfig(n_microbatches=8), optimizer=opt)
    params = jax.eval_shape(
        lambda: ST.init_stacked_params(cfg, jax.random.PRNGKey(0), plan))
    opt_state = jax.eval_shape(opt.init, params)
    p_sh, o_sh = RT.state_shardings(mesh, specs, opt_state)
    place = lambda tree, sh: jax.tree.map(
        lambda s, h: _shape(s.shape, s.dtype, h), tree, sh)
    tok = _shape((8, 1024), jnp.int32, NamedSharding(mesh, P("data", None)))
    compiled = step.lower(place(params, p_sh), place(opt_state, o_sh),
                          dict(tokens=tok, labels=tok)).compile()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 0 < used <= V5E_HBM_BYTES, used / 2 ** 30
