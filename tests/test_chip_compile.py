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
import hashlib
import json
import os
import re
from pathlib import Path

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
        _per_head_text(one_chip, variant, d)
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


def test_flash_decode_of_a_window_ring_compiles_for_v5e(one_chip):
    """The window-and-global cell's call in its window layers: the whole
    [3, 16, 8, 8192, 128] stack of window rings, 16 rows of 16 query heads
    a KV head (128 over 8), the layer an operand, the rows' lengths and
    offsets as `window_view` makes them, a window of 4096."""
    from tony_tpu.ops.decode_attention import flash_decode

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    rows = sds((16,), jnp.int32)
    ring = sds((3, 16, 8, 8192, 128), jnp.bfloat16)
    fn = jax.jit(lambda q, k, v, lengths, offsets, active: flash_decode(
        q, k, v, lengths, ring_offsets=offsets, active=active, window=4096,
        layer=2))
    _compiled_text(fn, sds((16, 8, 16, 128), jnp.bfloat16), ring, ring, rows,
                   rows, sds((16,), jnp.bool_))


def test_flash_decode_latent_compiles_for_v5e(one_chip):
    """The latent cell's call: the whole [5, 32, 4096, 576] stack of
    latent rows, 32 rows of 32 heads sharing the one row, the layer an
    operand, per-row lengths, offsets and the active mask. The stack is
    read where the TPU lays it out (positions minor: 576 is not a multiple
    of 128): the kernel's [R, M] view of it is a bitcast, and no copy of
    it is made."""
    from tony_tpu.ops.decode_attention import flash_decode_latent

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    stack, rows = (5, 32, 4096, 576), sds((32,), jnp.int32)
    fn = jax.jit(lambda q, c, lengths, offsets, active: flash_decode_latent(
        q, c, lengths, 4, v_dim=512, scale=192 ** -0.5, ring_offsets=offsets,
        active=active))
    compiled = fn.lower(sds((32, 32, 576), jnp.bfloat16),
                        sds(stack, jnp.bfloat16), rows, rows,
                        sds((32,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    made = re.findall(r" = bf16\[(?:5,32,4096,576|5,32,576,4096)\]\{[^}]*\} "
                      r"([\w-]+)\(", text)
    assert made and set(made) <= {"parameter", "bitcast"}, made
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


LATENT = (5, 32, 4096, 576)     # the latent cell's stack: layers, slots, M, R
_LATENT_MADE = r" = bf16\[(?:5,32,4096,576|5,32,576,4096)\]\{[^}]*\} ([\w-]+)\("


def test_write_latent_rows_compiles_for_v5e(one_chip):
    """The latent cell's row write: 32 new rows into the whole donated
    [5, 32, 4096, 576] stack at a traced cursor, one Mosaic kernel on the
    stack's [R, M] view (a bitcast of it) and aliased to it: the stack is
    made by nothing but its parameter, the bitcasts and the kernel."""
    from tony_tpu.ops.decode_attention import write_latent_rows

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn = jax.jit(lambda c, new, at: write_latent_rows(c, new, 4, at),
                 donate_argnums=0)
    compiled = fn.lower(sds(LATENT, jnp.bfloat16),
                        sds(LATENT[1::2], jnp.bfloat16),
                        sds((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    made = re.findall(_LATENT_MADE, text)
    assert set(made) == {"parameter", "bitcast", "custom-call"}, made
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_latent_write_and_read_stay_in_place_under_a_scan(one_chip):
    """Two decode steps of five latent layers each on a scan-carried,
    donated stack, every layer ``write_latent_rows`` then
    ``flash_decode_latent`` as ``_forward_with_cache`` calls them: ten
    kernels, and nothing in the compiled program copies, slices, sets or
    fuses an array of the stack's shape (one copy is 755 MB)."""
    from tony_tpu.ops.decode_attention import (flash_decode_latent,
                                               write_latent_rows)

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    ly, b, _, r = LATENT

    @functools.partial(jax.jit, donate_argnums=(2,))
    def two_steps(q, new, stack, lengths, offsets, active, at):
        def step(stack, _):
            total = 0.0
            for layer in range(ly):
                stack = write_latent_rows(stack, new, layer, at)
                total += jnp.sum(flash_decode_latent(
                    q, stack, lengths, layer, v_dim=512, scale=192 ** -0.5,
                    ring_offsets=offsets, active=active))
            return stack, total
        return jax.lax.scan(step, stack, None, length=2)

    rows = sds((b,), jnp.int32)
    text = _compiled_text(
        two_steps, sds((b, 32, r), jnp.bfloat16), sds((b, r), jnp.bfloat16),
        sds(LATENT, jnp.bfloat16), rows, rows, sds((b,), jnp.bool_),
        sds((), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * ly
    made = re.findall(_LATENT_MADE, text)
    assert made and not set(made) & {
        "copy", "dynamic-update-slice", "dynamic-slice", "fusion"}, made


PER_HEAD =json.loads((Path(__file__).parent / "fixtures"
                       / "flash_decode_per_head.json").read_text())


def _per_head_text(sharding, variant, d) -> str:
    """The serving cells' per-head ``flash_decode`` call (``ring``: 8 KV
    heads of 4 queries, 8 layers, a window; ``ring_mha``: 30 KV heads of
    one, 2 layers) lowered for the described chip, as the two compilers
    get it with nothing that names a source line: the Mosaic module of the
    kernel printed without locations, and the StableHLO around it without
    debug info or the kernel's serialized form (whose locations would
    differ). The program is compiled too, to a Mosaic kernel."""
    from jax._src import tpu_custom_call

    from tony_tpu.ops.decode_attention import flash_decode

    b, kvh, rep, m, layers, window = 16, 8, 4, 4096, 8, 4096
    if variant == "ring_mha":
        kvh, rep, layers, window = 30, 1, 2, 0
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    cache = sds((layers, b, kvh, m, d), jnp.bfloat16)
    rows = sds((b,), jnp.int32)
    fn = jax.jit(lambda q, ck, cv, lengths, offsets, active: flash_decode(
        q, ck, cv, lengths, ring_offsets=offsets, active=active,
        layer=layers - 1, window=window))
    kernels = []
    serialize = tpu_custom_call._lower_mosaic_module_to_asm

    def recorded(module, **kw):
        kernels.append(module.operation.get_asm(enable_debug_info=False))
        return serialize(module, **kw)

    tpu_custom_call._lower_mosaic_module_to_asm = recorded
    try:
        lowered = fn.lower(sds((b, kvh, rep, d), jnp.bfloat16), cache, cache,
                           rows, rows, sds((b,), jnp.bool_))
    finally:
        tpu_custom_call._lower_mosaic_module_to_asm = serialize
    assert "tpu_custom_call" in lowered.compile().as_text()
    assert len(kernels) == 1
    return kernels[0] + "\n" + re.sub(r"\\22body\\22: \\22[^\\]*\\22", "",
                                      lowered.as_text())


@pytest.mark.parametrize("variant", ("ring", "ring_mha"))
@pytest.mark.parametrize("d", HEAD_DIMS)
def test_flash_decode_per_head_compiles_as_it_did(one_chip, d, variant):
    """The per-head kernel and the program around it are the text they
    were before the latent form joined the module (the fixture says how
    the digests were taken; another jax or libtpu prints another text)."""
    import importlib.metadata

    versions = {"jax": jax.__version__,
                "libtpu": importlib.metadata.version("libtpu")}
    if versions != PER_HEAD["versions"]:
        pytest.skip(f"digests are of {PER_HEAD['versions']}")
    text = _per_head_text(one_chip, variant, d)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PER_HEAD["sha256"][f"{variant}[{d}]"]


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


@pytest.mark.parametrize("tokens, kernel", [
    (32, False), (1024, False), (32, True)])
def test_routed_expert_layer_compiles_for_v5e(one_chip, monkeypatch, tokens,
                                              kernel):
    """The routed expert layer (parallel/routed_experts.py) at the widths
    the benchmark serves, a decode step's 32 tokens and a prefill chunk
    round's 1024: XLA lowers ``jax.lax.ragged_dot`` to its own grouped
    matmul (a ``tpu_custom_call``), and a decode step's ``kernel`` path
    is two Mosaic kernels (whole-K, whole-N weight blocks in VMEM), each
    with no ``[experts, capacity, d]`` buffer and no copy of an expert
    matrix among the temporaries."""
    from tony_tpu.parallel.routed_experts import route, routed_ffn

    e, d, f, k = 256, 2048, 768, 8
    # the kernel asks the backend whether to interpret: a TPU's answer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def layer(x, router, bias, w_gu, w_down):
        chosen, w = route(x, router, bias, top_k=k, scale=2.5)
        return routed_ffn(x, chosen, w, w_gu, w_down, held=(0, e),
                          kernel=kernel)

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(layer).lower(
        sds((tokens, d), jnp.bfloat16), sds((d, e), jnp.float32),
        sds((e,), jnp.float32), sds((e, d, 2 * f), jnp.bfloat16),
        sds((e, f, d), jnp.bfloat16)).compile()
    text = compiled.as_text()
    if kernel:
        assert "ragged-dot" not in text
        assert text.count('custom_call_target="tpu_custom_call"') == 2
    else:
        assert text.count("ragged-dot") >= 2
    # the sorted tokens, their gate-and-up and the combine, not the experts
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * tokens * k * d


@pytest.mark.parametrize("e, held, d, f, k, tiles", [
    (128, (0, 16), 4096, 4096, 8, (512, 512)),      # command-a-plus' share
    (64, (0, 64), 3584, 1024, 4, (512, 1792)),      # xing4's whole layer
])
def test_a_share_of_wide_experts_compiles_for_v5e(one_chip, monkeypatch, e,
                                                  held, d, f, k, tiles):
    """A decode step's kernel path over wide experts (16 of 128 held, 4096
    x 2 x 4096 gate-and-up; all 64 of 3584 x 2 x 1024), 16 tokens: an
    expert's whole matrix would not fit the kernel's scoped VMEM twice, so
    its weight block is an N tile of it (`weight_tile`), and the two
    Mosaic kernels compile."""
    from tony_tpu.parallel.routed_experts import (WEIGHT_BLOCK_BYTES, route,
                                                  routed_ffn, weight_tile)

    assert 2 * d * f * 2 > WEIGHT_BLOCK_BYTES
    assert (weight_tile(d, 2 * f, 2), weight_tile(f, d, 2)) == tiles
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def layer(x, router, bias, w_gu, w_down):
        chosen, w = route(x, router, bias, top_k=k, scale=1.0)
        return routed_ffn(x, chosen, w, w_gu, w_down, held=held, kernel=True)

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = jax.jit(layer).lower(
        sds((16, d), jnp.bfloat16), sds((d, e), jnp.float32),
        sds((e,), jnp.float32), sds((held[1], d, 2 * f), jnp.bfloat16),
        sds((held[1], f, d), jnp.bfloat16)).compile().as_text()
    assert "ragged-dot" not in text
    assert text.count('custom_call_target="tpu_custom_call"') == 2
