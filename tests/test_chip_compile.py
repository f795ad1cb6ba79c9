"""The main path's Pallas kernels, compiled by the TPU's own compiler for a
chip that is described and not attached (guide on-chip-measurement §2).

Interpret-mode tests cannot see what Mosaic refuses — a slice not aligned
to the tiling, too much VMEM, a kernel GSPMD cannot partition. These
compiles can, at no chip time: each lowers one kernel at a real width for
a described ``v5e:2x2`` and requires ``tpu_custom_call`` in the program.
head_dim 64 is the small published Llamas' head size (Llama-3.2-1B); 128
is the flagship's.

The topology is described inside a module-scoped fixture, never at import:
only one process may load libtpu, and every xdist worker imports this file.
"""

from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

HEAD_DIMS = (128, 64)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _qkv(d, sharding, b=2, h=8, l=2048):
    return [jax.ShapeDtypeStruct((b, h, l, d), jnp.bfloat16,
                                 sharding=sharding)] * 3


@pytest.mark.parametrize("window", (None, 512))
@pytest.mark.parametrize("d", HEAD_DIMS)
def test_flash_forward_compiles_for_v5e(one_chip, d, window):
    from tony_tpu.ops.attention import _flash_fwd

    fwd = functools.partial(_flash_fwd, causal=True, scale=None,
                            interpret=False, window=window)
    _compiled_text(jax.jit(fwd), *_qkv(d, one_chip))


@pytest.mark.parametrize("window", (None, 512))
@pytest.mark.parametrize("d", HEAD_DIMS)
def test_flash_backward_compiles_for_v5e(one_chip, d, window):
    from tony_tpu.ops.attention import _flash_bwd

    b, h, l = 2, 8, 2048
    q, k, v = _qkv(d, one_chip, b, h, l)
    lse = jax.ShapeDtypeStruct((b, h, l), jnp.float32, sharding=one_chip)
    bwd = functools.partial(_flash_bwd, causal=True, scale=None,
                            interpret=False, window=window)
    # (q, k, v, out, lse, g_out)
    _compiled_text(jax.jit(bwd), q, k, v, q, lse, q)


@pytest.mark.parametrize("variant", ("bf16", "int8", "layer_window", "ring",
                                     "ring_mha"))
@pytest.mark.parametrize("d", HEAD_DIMS)
def test_flash_decode_compiles_for_v5e(one_chip, d, variant):
    """``ring`` is the serving cell's call: the 8-layer stack of 16 slots x
    4096, per-row lengths, ring offsets and the active mask, the sliding
    window of the published config. ``ring_mha`` is the hybrid cell's: 30
    KV heads with one query head each, the two full-attention layers'
    stack, no window."""
    from tony_tpu.ops.decode_attention import flash_decode

    b, kvh, rep, m, layers = 2, 8, 4, 4096, 2
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    q = sds((b, kvh, rep, d), jnp.bfloat16)
    length = sds((), jnp.int32)
    if variant in ("ring", "ring_mha"):
        b, layers, window = 16, 8, 4096
        if variant == "ring_mha":
            kvh, rep, layers, window = 30, 1, 2, 0
        cache = sds((layers, b, kvh, m, d), jnp.bfloat16)
        rows = sds((b,), jnp.int32)
        fn = jax.jit(lambda q, ck, cv, lengths, offsets, active: flash_decode(
            q, ck, cv, lengths, ring_offsets=offsets, active=active,
            layer=layers - 1, window=window))
        _compiled_text(fn, sds((b, kvh, rep, d), jnp.bfloat16), cache, cache,
                       rows, rows, sds((b,), jnp.bool_))
    elif variant == "int8":
        cache = sds((b, kvh, m, d), jnp.int8)
        scale = sds((b, kvh, m), jnp.bfloat16)
        _compiled_text(flash_decode, q, cache, cache, length, scale, scale)
    elif variant == "layer_window":
        cache = sds((layers, b, kvh, m, d), jnp.bfloat16)
        fn = jax.jit(functools.partial(flash_decode, layer=1, window=512))
        _compiled_text(fn, q, cache, cache, length)
    else:
        cache = sds((b, kvh, m, d), jnp.bfloat16)
        _compiled_text(flash_decode, q, cache, cache, length)


def test_flash_under_a_four_chip_mesh_compiles_for_v5e(topo):
    """GSPMD refuses to partition a Mosaic kernel; the model's flash path
    wraps it in a shard_map over batch and heads (models/transformer.py).
    Llama-3.2-1B's attention shape on a data x tensor mesh of the 2x2."""
    from tony_tpu.models.transformer import _partition_over_batch_and_heads
    from tony_tpu.ops.attention import _flash_fwd

    mesh = Mesh(np.asarray(topo.devices, dtype=object).reshape(2, 2),
                ("fsdp", "tensor"))
    sharding = NamedSharding(mesh, P("fsdp", None, "tensor", None))
    q = jax.ShapeDtypeStruct((4, 2048, 32, 64), jnp.bfloat16,
                             sharding=sharding)

    def attn(q, k, v):                   # [B, L, H, D], as the model calls
        t = lambda x: x.transpose(0, 2, 1, 3)
        return t(_flash_fwd(t(q), t(k), t(v), True, None,
                            interpret=False)[0])

    fn = jax.jit(_partition_over_batch_and_heads(attn, mesh, q.shape))
    _compiled_text(fn, q, q, q)


STATE = (6, 16, 30, 96, 192)    # the hybrid cell's: layers, slots, H, d_k, d_v


def _delta_args(sds):
    _, b, h, dk, dv = STATE
    f32 = jnp.float32
    return (sds((b, h, dk), f32), sds((b, h, dk), f32), sds((b, h, dv), f32),
            sds((b, h), f32), sds((b, h), f32), sds(STATE, f32),
            sds((b,), jnp.bool_))


@pytest.mark.parametrize("head_block", (None, 30))
def test_gated_delta_decode_compiles_for_v5e(one_chip, head_block):
    """The hybrid cell's call: the whole stack of six layers' states as
    the operand, 16 slots, a live list that is a runtime value."""
    from tony_tpu.ops.gated_delta import gated_delta_decode

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn = jax.jit(functools.partial(gated_delta_decode, layer=5,
                                   head_block=head_block))
    _compiled_text(fn, *_delta_args(sds))


def test_gated_delta_decode_stays_in_place_under_a_scan(one_chip):
    """Two decode steps of six calls each on a scan-carried, donated
    stack: nothing in the compiled program copies, slices or sets an
    array of the stack's shape (one copy is 283 MB, more than the kernel
    saves a step)."""
    from tony_tpu.ops.gated_delta import gated_delta_decode

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)

    @functools.partial(jax.jit, donate_argnums=(5,))
    def two_steps(q, k, v, log_alpha, beta, state, active):
        def step(state, _):
            total = 0.0
            for layer in range(STATE[0]):
                o, state = gated_delta_decode(q, k, v, log_alpha, beta,
                                              state, active, layer=layer)
                total += jnp.sum(o)
            return state, total
        return jax.lax.scan(step, state, None, length=2)

    text = _compiled_text(two_steps, *_delta_args(sds))
    assert text.count('custom_call_target="tpu_custom_call"') == STATE[0]
    made = re.findall(r" = f32\[%s\]\{[^}]*\} ([\w-]+)\(" % ",".join(
        map(str, STATE)), text)
    assert made and set(made) <= {"parameter", "get-tuple-element"}, made


@pytest.mark.parametrize("tokens", [32, 1024])
def test_routed_expert_layer_compiles_for_v5e(one_chip, tokens):
    """The routed expert layer (parallel/routed_experts.py) at the widths
    the benchmark serves, a decode step's 32 tokens and a prefill chunk
    round's 1024: XLA lowers ``jax.lax.ragged_dot`` to its own grouped
    matmul (a ``tpu_custom_call``), with no ``[experts, capacity, d]``
    buffer and no copy of an expert matrix among the temporaries."""
    from tony_tpu.parallel.routed_experts import route, routed_ffn

    e, d, f, k = 256, 2048, 768, 8

    def layer(x, router, bias, w_gu, w_down):
        chosen, w = route(x, router, bias, top_k=k, scale=2.5)
        return routed_ffn(x, chosen, w, w_gu, w_down, held=(0, e))

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(layer).lower(
        sds((tokens, d), jnp.bfloat16), sds((d, e), jnp.float32),
        sds((e,), jnp.float32), sds((e, d, 2 * f), jnp.bfloat16),
        sds((e, f, d), jnp.bfloat16)).compile()
    assert compiled.as_text().count("ragged-dot") >= 2
    # the sorted tokens, their gate-and-up and the combine, not the experts
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * tokens * k * d
