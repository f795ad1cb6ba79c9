"""Driver: a decoder of sliding-window and global attention layers in a
parallel block with routed and shared experts (the ``cohere2_moe`` block)
served in-process through ``ServeApp.submit_async``, through the entry
points every serving driver uses: ``prepare_decode`` -> ``SlotServer`` ->
``ServeApp``.

Its own: the configuration's mapping onto the program's config and the
seed's weights in the program's tree (``weights/window_moe_decoder.py``, a
layer at a time). The rest is ``drivers/serve_mla_moe.py``'s run, loaded
from that file as a module of its own with this configuration's hooks in
place of the latent decoder's: `transformer_config`, `program_params`, the
cost arithmetic (``costs/window_moe_decoder.py``) and, for the count below,
its submits, its warm-up and its sample (`_drain` refuses a run that no
longer calls them by those names). That run is the saturated drain of a
closed loop (every counted gap is of a full server) and the routed
comparison (every request asks for the experts it chose, the reference
computes with them, ``routing_margin_gap`` checks the choices): the same
numbers under the same names, computed once.

One number of its own, ``tokens_below_own_first``: every request also asks
for its top logprob, and the number counts the compared tokens whose own
logprob lies below the program's first at the position where it was
produced (0 for a greedy server, exactly: the sample and the logprobs are
read off one row). The model's greedy tokens are diverse (the
configuration's ``query_init_scale``), so the logits are not peaked: at
one position in a few thousand some token ties the best, and bfloat16
rounding moves a sharp attention's logits by tenths (the reference with
bfloat16 activations reads the sound runs' gaps). A token altered where it
is produced can thus hide inside the logit limits at such a position; it
cannot hide from this count, which the planted fault reads as 1.
"""

from __future__ import annotations

import functools
import importlib.util

import lib

COSTS = "costs/window_moe_decoder.py"


def transformer_config(cfg: dict, max_len: int):
    import jax.numpy as jnp
    from tony_tpu.models.transformer import TransformerConfig

    weights = lib.load("weights/window_moe_decoder.py")
    lib.load("reference/" + cfg["reference"] + ".py")._check(cfg)
    dt = cfg["dtype"]
    n = cfg["num_hidden_layers"]
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=n, n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], attn_head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], max_seq_len=max_len,
        # the global layers are NoPE; the window layers rotate
        # (rope_gptj: the adjacent pairs)
        rope_theta=None, win_rope_theta=float(cfg["rope_theta"]),
        rope_interleave=True, win_size=cfg["sliding_window"],
        norm_type="layer", norm_eps=float(cfg["layer_norm_eps"]),
        norm_order="parallel",
        layer_kinds=tuple(weights.layer_kinds(cfg)),
        mlp_kinds=("routed",) * n, moe_experts=weights.n_experts(cfg),
        moe_top_k=cfg["num_experts_per_tok"], moe_ff=cfg["intermediate_size"],
        moe_shared=cfg["num_shared_experts"], moe_shared_mean=True,
        moe_held=weights.experts_held(cfg),
        dtype=jnp.dtype(dt["activations"]),
        param_dtype=jnp.dtype(dt["weights"]))


def program_layer(cfg: dict, lw: dict) -> tuple:
    """One layer of ``weights/window_moe_decoder.py`` in the program's
    format -> (its attention, its MLP): the heads split out of the
    projections' widths, gate and up side by side in one matrix (the
    experts' [count, d, 2 f], the shared experts' [d, 2 S f]), a selection
    bias of zeros (the router has none)."""
    import jax.numpy as jnp

    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    mixer = {"attn_norm": lw["attn_norm"],
             "wq": lw["wq"].reshape(d, h, hd), "wk": lw["wk"].reshape(d, kv, hd),
             "wv": lw["wv"].reshape(d, kv, hd), "wo": lw["wo"].reshape(h, hd, d)}
    return mixer, {
        "router": lw["router"],
        "router_bias": jnp.zeros(lw["router"].shape[1:], jnp.float32),
        "we_gu": jnp.concatenate([lw["experts_gate"], lw["experts_up"]], -1),
        "we_down": lw["experts_down"],
        "ws_gu": jnp.concatenate([lw["shared_gate"], lw["shared_up"]], -1),
        "ws_down": lw["shared_down"]}


def program_params(cfg: dict, seed: int, dtype) -> dict:
    """The seed's weights in the program's tree, made a layer at a time
    (one jitted call a layer: the float32 draw of a layer's experts is
    never live beside another's), the attention stacked by kind, the MLPs
    a list a layer; the unembedding is the embedding's transpose."""
    import jax
    import jax.numpy as jnp

    weights = lib.load("weights/window_moe_decoder.py")
    key = weights.seed_key(seed)
    make = jax.jit(lambda key, i: program_layer(
        cfg, weights.layer(key, cfg, i, dtype)))
    mixers: dict = {}
    mlps = []
    for i, kind in enumerate(weights.layer_kinds(cfg)):
        mixer, mlp = make(key, jnp.int32(i))
        mixers.setdefault(kind, []).append(mixer)
        mlps.append(mlp)
    stack = jax.jit(lambda *ls: jax.tree.map(lambda *a: jnp.stack(a), *ls))
    embed = jax.jit(lambda k: weights.embed(k, cfg, dtype))(key)
    return {"embed": embed,
            "layers": {**{kind: stack(*ls) for kind, ls in mixers.items()},
                       "routed": mlps},
            "final_norm": weights.final_norm(cfg, dtype),
            "unembed": jax.jit(jnp.transpose)(embed)}


class _Serve:
    """``drivers/serve.py`` as the drain's run calls it: the warm-up's
    requests ask for their top logprob, as the window's do, so that the
    decode block's variant that carries logprobs is built before it."""

    def __getattr__(self, name):
        return getattr(lib.load("drivers/serve.py"), name)

    @staticmethod
    def _warm_up(server, request_cls, engine: dict, vocab: int) -> None:
        lib.load("drivers/serve.py")._warm_up(
            server, functools.partial(request_cls, logprobs=1), engine, vocab)


class _Lib:
    """``lib`` as the drain's run calls it: this configuration's cost
    arithmetic where it loads the latent decoder's, the warm-up of
    `_Serve`."""

    def __getattr__(self, name):
        return getattr(lib, name)

    @staticmethod
    def load(rel: str):
        if rel == "drivers/serve.py":
            return _Serve()
        return lib.load(COSTS if rel == "costs/mla_moe_decoder.py" else rel)


def _drain():
    """``drivers/serve_mla_moe.py`` as a module of its own (the one
    ``lib.load`` keeps is left as it is), with this configuration's
    hooks. The hooks are found by name: a run that no longer reads them
    so would run the latent decoder's, so that is refused here."""
    path = lib.ROOT / "drivers/serve_mla_moe.py"
    source = path.read_text()
    for used in ('lib.load("costs/mla_moe_decoder.py")',
                 "transformer_config(cfg,", "program_params(cfg,",
                 'serve._warm_up(server, Request,',
                 "_AskingRoutes(app)", "c = compared(cfg, mix,",
                 'notes["fault_token_altered"]'):
        if used not in source:
            raise RuntimeError(f"drivers/serve_mla_moe.py's run no longer "
                               f"reads {used!r}: this driver's hooks would "
                               f"not be the ones it runs")
    spec = importlib.util.spec_from_file_location(
        "bench_drivers_serve_window_moe_drain", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.lib = _Lib()
    mod.transformer_config = transformer_config
    mod.program_params = program_params
    return mod


def below_own_first(sample: list) -> int:
    """The sample's served tokens whose logprob lies below the first of
    the program's own at that position (``Completion.logprobs``)."""
    n = 0
    for r in sample:
        for entry in r.comp.logprobs or ():
            n += entry["logprob"] < entry["top"][1][0]
        if len(r.comp.logprobs or ()) != len(r.comp.tokens):
            raise ValueError("a compared request came back without a "
                             "logprob for every token")
    return n


def run(ctx) -> dict:
    engine = ctx.cfg["engine"]
    from tony_tpu.models.generate import window_ring

    ring = window_ring(transformer_config(ctx.cfg, engine["max_len"]),
                       engine["max_len"], engine["prefill_chunk"])
    if ring != engine.get("window_ring", ring):
        raise ValueError(f"the configuration's window_ring "
                         f"{engine['window_ring']} is not the engine's {ring}")
    drain = _drain()
    found = {}

    class AskingFirst(drain._AskingRoutes):
        def submit_async(self, *args, **kw):
            return super().submit_async(*args, logprobs=1, **kw)

    def compared(cfg, mix, sample):
        found["below"] = below_own_first(sample)
        return routed_compared(cfg, mix, sample)

    routed_compared = drain.compared
    drain._AskingRoutes, drain.compared = AskingFirst, compared
    out = drain.run(ctx)
    if "below" in found:
        checks = out["checks"]
        checks.add("tokens_below_own_first", found["below"])
        altered = out["notes"].get("fault_token_altered")
        if altered is not None:
            # one token altered where it is produced lies below the
            # program's first there: this count reads 1 for it
            out["notes"]["fault_token_altered"] = checks.judge({
                "logit_gap_max":
                    altered["compared"]["logit_gap_max"]["value"],
                "tokens_below_own_first": 1})
    return out
