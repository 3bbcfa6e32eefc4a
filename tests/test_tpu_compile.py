"""Compile each Pallas kernel at real widths for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed with jax, compiles for a
chip that is described and not attached, so a block shape or a relayout
the chip's compiler refuses fails here and not on the chip.  Interpret
mode (tests/test_kernels.py) cannot see either.  The topology is described
inside a fixture, never at import: only one process at a time may load the
TPU library, and every test worker imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.ops import decode_attention_op
from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.kmeans.ops import kmeans_assign_op
from repro.kernels.selective_scan.ops import selective_scan_op


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back without
    # one, so keep these compiles out of any persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled.as_text()


def _s(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_kmeans_assign_compiles_for_v5e(one_chip):
    """The paper's scenario (i): 1M points, D=8, k=50."""
    hlo = _compile(functools.partial(kmeans_assign_op, impl="pallas"),
                   _s(one_chip, (1_000_000, 8)), _s(one_chip, (50, 8)))
    assert "tpu_custom_call" in hlo


def test_decode_attention_compiles_for_v5e(one_chip):
    """Llama-class decode: 8 rows, 2048 cache slots, 32 q / 8 kv heads."""
    b, sc, nq, nkv, hd = 8, 2048, 32, 8, 64
    bf = jnp.bfloat16
    hlo = _compile(functools.partial(decode_attention_op, impl="pallas"),
                   _s(one_chip, (b, nq, hd), bf),
                   _s(one_chip, (b, sc, nkv, hd), bf),
                   _s(one_chip, (b, sc, nkv, hd), bf),
                   _s(one_chip, (b, sc), jnp.int32),
                   _s(one_chip, (b,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles_for_v5e(one_chip):
    b, s, nq, nkv, hd = 1, 2048, 32, 8, 64
    bf = jnp.bfloat16
    hlo = _compile(functools.partial(flash_attention_op, impl="pallas"),
                   _s(one_chip, (b, s, nq, hd), bf),
                   _s(one_chip, (b, s, nkv, hd), bf),
                   _s(one_chip, (b, s, nkv, hd), bf))
    assert "tpu_custom_call" in hlo


def _scan_hlo(sharding, s):
    b, di, n = 1, 8192, 16
    bf = jnp.bfloat16
    return _compile(selective_scan_op,
                    _s(sharding, (b, s, di), bf), _s(sharding, (b, s, di)),
                    _s(sharding, (di, n)), _s(sharding, (b, s, n), bf),
                    _s(sharding, (b, s, n), bf), _s(sharding, (di,)),
                    _s(sharding, (b, di, n)))


def test_selective_scan_compiles_for_v5e(one_chip):
    """falcon-mamba-7b widths (d_inner 8192, state 16) with bf16
    activations, whose packed rows need tile-aligned loads, and float32 dt
    and state as the prefill gives them."""
    assert "tpu_custom_call" in _scan_hlo(one_chip, 2048)


def test_selective_scan_compiles_unaligned_for_v5e(one_chip):
    """The traffic's longest prompt, 3128 tokens: padded to 3136 inside
    the scan, so its last chunk of 256 steps holds 64."""
    assert "tpu_custom_call" in _scan_hlo(one_chip, 3128)


def test_prefill_runs_the_scan_kernel_for_v5e(one_chip, monkeypatch):
    """A one-layer falcon-width prefill, lowered as on a TPU, holds the
    kernel: the platform check sees the CPU here, so it is set to TPU."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import ssm
    from repro.models.model import build_model
    cfg = dataclasses.replace(get_config("falcon_mamba_7b"), num_layers=1,
                              vocab_size=1024)
    model = build_model(cfg)
    monkeypatch.setattr(ssm, "_kernel_scan", lambda: True)
    params = jax.tree.map(
        lambda t: _s(one_chip, t.shape, t.dtype),
        jax.eval_shape(model.init, jax.random.key(0)))
    hlo = _compile(lambda p, t: model.prefill(p, {"tokens": t}, 1024),
                   params, _s(one_chip, (1, 1021), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("tokens", [32, 3128], ids=["decode", "refill"])
def test_dropless_experts_compile_for_v5e(one_chip, tokens):
    """Jamba2-Mini's expert layer at published widths, 8 of 16 experts
    held, for a decode step's 32 tokens and the longest refill: the
    grouped matmuls lower to the chip's ragged dot, not to a dense
    expansion over every held expert."""
    import dataclasses

    from repro.configs.jamba2_mini import CONFIG
    from repro.models.common import ParamSpec
    from repro.models.moe import dropless_moe, moe_specs
    cfg = dataclasses.replace(CONFIG, moe=dataclasses.replace(
        CONFIG.moe, held=(0, 8)))
    params = jax.tree.map(lambda s: _s(one_chip, s.shape, s.dtype),
                          moe_specs(cfg),
                          is_leaf=lambda s: isinstance(s, ParamSpec))
    # at the precision the program serves in: conftest.py sets "highest"
    # for the CPU tests, and the chip's ragged dot refuses bf16 operands
    # at "highest" (Mosaic: "Bad lhs type")
    with jax.default_matmul_precision("default"):
        hlo = _compile(lambda p, x: dropless_moe(p, x, cfg)[0], params,
                       _s(one_chip, (1, tokens, cfg.d_model), jnp.bfloat16))
    assert "ragged" in hlo
