"""Compile the served path for a TPU v5e without a chip attached.

jaxlib ships the TPU compiler, which compiles for a *described* ``v5e:2x2``
topology.  That catches what interpret mode cannot — tiles not aligned to
the (8, 128) layout, more scoped VMEM than a kernel may use, a step that
does not fit the chip's 16 GB — at the shapes the server runs.  Nothing
executes.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and every
pytest-xdist worker imports every test file.  Keep these tests in this one
file, so the worker given it is the only one that loads the library.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import for_mode, get_config
from repro.core.energy import CHIP_PEAKS
from repro.core.types import RouterConfig
from repro.kernels.featurize.ops import _embed_jit, _pick_block
from repro.kernels.linucb.ops import _scores_jit
from repro.models import api
from repro.serving.engine import greedy_chunk_step, greedy_step

HASH_DIM, EMBED_DIM, FEATURES = 2048, 384, 128     # EmbeddingModel defaults
SERVE_BATCH, SERVE_LEN, CHUNK = 4, 1024, 8          # chip_smoke.py's pool
HBM_BYTES = CHIP_PEAKS["TPU v5 lite"].hbm_bytes


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("n_queries", [1, 64, 128])
def test_featurize_kernel_compiles_for_v5e(one_chip, n_queries):
    """Q = 1 and 64 queries; 128 rows is the fused router's "both" mode
    (full texts and instruction slices stacked) for 64 queries."""
    q = jax.ShapeDtypeStruct((n_queries, FEATURES), jnp.int32)
    w = jax.ShapeDtypeStruct((n_queries, FEATURES), jnp.float32)
    proj = jax.ShapeDtypeStruct((HASH_DIM, EMBED_DIM), jnp.float32)
    compiled = _embed_jit.lower(
        *_on(one_chip, (q, w, proj)), bq=_pick_block(n_queries, 8),
        lb=FEATURES, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_queries", [1, 64])
def test_linucb_kernel_compiles_for_v5e(one_chip, n_queries):
    cfg = RouterConfig()
    m, d = cfg.max_arms, cfg.context_dim
    shapes = (jax.ShapeDtypeStruct((m, d, d), jnp.float32),
              jax.ShapeDtypeStruct((m, d), jnp.float32),
              jax.ShapeDtypeStruct((n_queries, d), jnp.float32))
    compiled = _scores_jit.lower(*_on(one_chip, shapes), alpha=0.1,
                                 bq=n_queries, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _serving(arch, sharding, batch=SERVE_BATCH, **overrides):
    cfg = dataclasses.replace(for_mode(get_config(arch, **overrides),
                                       "serve"), kv_update="where")
    params = _on(sharding, api.param_shapes(cfg))
    cache = _on(sharding, jax.eval_shape(
        lambda: api.init_cache(cfg, batch, SERVE_LEN)))
    return cfg, params, cache


def _fits_hbm(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    return total < HBM_BYTES


@pytest.mark.parametrize("arch", ["h2o-danube-3-4b", "rwkv6-1.6b"])
def test_published_width_serve_step_compiles_for_v5e(one_chip, arch):
    cfg, params, cache = _serving(arch, one_chip)
    tokens = jax.ShapeDtypeStruct((SERVE_BATCH, 1), jnp.int32,
                                  sharding=one_chip)
    compiled = greedy_step.lower(params, cache, tokens, cfg=cfg).compile()
    assert _fits_hbm(compiled)


def test_published_width_prefill_chunk_compiles_for_v5e(one_chip):
    cfg, params, cache = _serving("h2o-danube-3-4b", one_chip)
    assert "k" in cache                 # full-depth cache: chunking applies
    tokens = jax.ShapeDtypeStruct((SERVE_BATCH, CHUNK), jnp.int32,
                                  sharding=one_chip)
    n_active = jax.ShapeDtypeStruct((SERVE_BATCH,), jnp.int32,
                                    sharding=one_chip)
    compiled = greedy_chunk_step.lower(params, cache, tokens, n_active,
                                       cfg=cfg).compile()
    assert _fits_hbm(compiled)


@pytest.mark.parametrize("chunk", [1, CHUNK])
def test_deepseek_share_serving_steps_compile_for_v5e(one_chip, chunk):
    """deepseek-v2-lite at one EP8 chip's share (experts 0-7 of 64) with
    the benchmark cell's 16 slots: the one-token and the chunk step fit
    the chip, and neither copies the whole latent cache or a whole stack
    of expert weights (a layout change of either per tick would cost as
    much as the step's own reads)."""
    batch = 16
    cfg, params, cache = _serving("deepseek-v2-lite", one_chip, batch=batch,
                                  experts_held=8)
    assert set(cache) == {"k", "length"}
    tokens = jax.ShapeDtypeStruct((batch, chunk), jnp.int32,
                                  sharding=one_chip)
    if chunk == 1:
        compiled = greedy_step.lower(params, cache, tokens, cfg=cfg).compile()
    else:
        n_active = jax.ShapeDtypeStruct((batch,), jnp.int32,
                                        sharding=one_chip)
        compiled = greedy_chunk_step.lower(params, cache, tokens, n_active,
                                           cfg=cfg).compile()
    assert _fits_hbm(compiled)
    whole = {",".join(map(str, cache["k"].shape))} | {
        ",".join(map(str, params["layers"]["moe"][k].shape))
        for k in ("wi_gate", "wi_up", "wo")}
    copies = [line for line in compiled.as_text().splitlines()
              if " copy(" in line
              and any(f"[{shape}]" in line for shape in whole)]
    assert not copies, copies[:2]
