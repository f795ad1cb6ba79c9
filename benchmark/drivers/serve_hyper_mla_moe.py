"""Driver: a latent-attention decoder with routed experts, manifold-
constrained hyper-connections and YaRN rope (``model_type`` ``xing4_0``)
served in-process through ``ServeApp.submit_async``, through the entry
points every serving driver uses: ``prepare_decode`` -> ``SlotServer`` ->
``ServeApp``.

Its own: the configuration's mapping onto the program's config (the
latent decoder's, with ``rope_scaling`` "yarn" and the ``hc_*`` fields)
and the seed's weights in the program's tree
(``weights/hyper_mla_moe_decoder.py``, a layer at a time: the block's in
``drivers/serve_mla_moe.py``'s format, the hyper-connections a dict a
layer). The rest is ``drivers/serve_mla_moe.py``'s run, loaded from that
file as a module of its own with this configuration's hooks in place of
the latent decoder's, as ``drivers/serve_window_moe.py`` does it:
`transformer_config`, `program_params` and the cost arithmetic
(``costs/hyper_mla_moe_decoder.py``). That run is the saturated drain of
a closed loop and the routed comparison (every request asks for the
experts it chose, the reference computes with them, ``routing_margin_gap``
checks the choices).

Two controls of its own where the run asks for controls: the reference
with every stream mix the identity ("h_res_identity": the plain residual
repeated n times) and with one alternation of the Sinkhorn projection
("sinkhorn_one") in the program's place, each judged by the cell's limits
under ``notes["fault_hyper_<name>"]``: both have to fail.

``tokens_below_own_first`` as ``drivers/serve_window_moe.py`` counts it
(its warm-up, its requests asking for their top logprob, its count): the
compared tokens whose logprob lies below the program's own first where
they were produced, 0 for a greedy server. A token altered where it is
produced reads 1 there, whether or not the logit limits see it. And
``notes["served_tokens_distinct_pct"]``: the distinct share of the
compared served tokens, how diverse the greedy tokens are.
"""

from __future__ import annotations

import dataclasses
import importlib.util

import lib

COSTS = "costs/hyper_mla_moe_decoder.py"
WEIGHTS = "weights/hyper_mla_moe_decoder.py"
HYPER_FAULTS = ("h_res_identity", "sinkhorn_one")


def transformer_config(cfg: dict, max_len: int):
    weights = lib.load(WEIGHTS)
    base = lib.load("drivers/serve_mla_moe.py").transformer_config(
        weights.block_cfg(cfg), max_len)
    sc = cfg["rope_scaling"]
    return dataclasses.replace(
        base,
        rope_scaling=("yarn", float(sc["factor"]), float(sc["beta_fast"]),
                      float(sc["beta_slow"]),
                      int(sc["original_max_position_embeddings"]),
                      float(sc["mscale"]), float(sc["mscale_all_dim"])),
        hc_mult=cfg["hc_mult"], hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hc_eps=float(cfg["hc_eps"]),
        hc_res_clamp=float(cfg["mhc_h_res_clamp_max"]))


def program_params(cfg: dict, seed: int, dtype) -> dict:
    """The seed's weights in the program's tree, made a layer at a time
    (one jitted call a layer), the mixers then stacked, the MLPs and the
    hyper-connections a list a layer."""
    import jax
    import jax.numpy as jnp

    weights = lib.load(WEIGHTS)
    block = lib.load("drivers/serve_mla_moe.py").program_layer
    key = weights.seed_key(seed)
    held = cfg.get("experts_held")

    def one(key, i, kind):
        lw = weights.layer(key, cfg, i, kind, dtype, held)
        mixer, mlp = block(cfg, lw, kind)
        return mixer, mlp, {"phi": lw["hc_phi"], "bias": lw["hc_bias"],
                            "alpha": lw["hc_alpha"]}

    make = {kind: jax.jit(lambda key, i, kind=kind: one(key, i, kind))
            for kind in weights.KINDS}
    mixers, mlps, hyper = [], {}, []
    for i, kind in enumerate(weights.layer_kinds(cfg)):
        mixer, mlp, hc = make[kind](key, jnp.int32(i))
        mixers.append(mixer)
        mlps.setdefault(kind, []).append(mlp)
        hyper.append(hc)
    stack = jax.jit(lambda *ls: jax.tree.map(lambda *a: jnp.stack(a), *ls))
    bcfg = weights.block_cfg(cfg)
    return {"embed": jax.jit(lambda k: weights.embed(k, bcfg, dtype))(key),
            "layers": {"latent": stack(*mixers), **mlps, "hyper": hyper},
            "final_norm": weights.final_norm(bcfg, dtype),
            "unembed": jax.jit(lambda k: weights.unembed(k, bcfg, dtype))(key)}


class _Lib:
    """``lib`` as the drain's run calls it: this configuration's cost
    arithmetic where it loads the latent decoder's, the warm-up of
    ``drivers/serve_window_moe.py`` (its requests ask for their top
    logprob)."""

    def __getattr__(self, name):
        return getattr(lib, name)

    @staticmethod
    def load(rel: str):
        if rel == "drivers/serve.py":
            return lib.load("drivers/serve_window_moe.py")._Serve()
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
                 'serve._warm_up(server, Request,', "_AskingRoutes(app)",
                 "c = compared(cfg, mix,", "def routed_gaps(",
                 'notes["fault_token_altered"]'):
        if used not in source:
            raise RuntimeError(f"drivers/serve_mla_moe.py's run no longer "
                               f"reads {used!r}: this driver's hooks would "
                               f"not be the ones it runs")
    spec = importlib.util.spec_from_file_location(
        "bench_drivers_serve_hyper_mla_moe_drain", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.lib = _Lib()
    mod.transformer_config = transformer_config
    mod.program_params = program_params
    return mod


def run(ctx) -> dict:
    import numpy as np

    window = lib.load("drivers/serve_window_moe.py")
    drain = _drain()
    found = {}
    routed_compared = drain.compared

    class AskingFirst(drain._AskingRoutes):
        def submit_async(self, *args, **kw):
            return super().submit_async(*args, logprobs=1, **kw)

    def compared(cfg, mix, sample):
        found["below"] = window.below_own_first(sample)
        found["c"] = routed_compared(cfg, mix, sample)
        return found["c"]

    drain._AskingRoutes, drain.compared = AskingFirst, compared
    out = drain.run(ctx)
    if "c" not in found:
        return out
    checks = out["checks"]
    checks.add("tokens_below_own_first", found["below"])
    served = np.asarray(found["c"]["served"])[found["c"]["valid"]]
    out["notes"]["served_tokens_distinct_pct"] = (
        100.0 * np.unique(served).size / max(served.size, 1))
    altered = out["notes"].get("fault_token_altered")
    if altered is not None:
        # one token altered where it is produced lies below the program's
        # first there: this count reads 1 for it
        out["notes"]["fault_token_altered"] = checks.judge({
            "logit_gap_max": altered["compared"]["logit_gap_max"]["value"],
            "tokens_below_own_first": 1})
    if ctx.control:
        for fault in HYPER_FAULTS:
            numbers = drain.routed_gaps(ctx.cfg, ctx.seed, found["c"],
                                        fault=fault)
            out["notes"]["fault_hyper_" + fault] = checks.judge({
                "logit_gap_max": float(np.max(numbers["gaps"])),
                "logit_gap_mean": float(np.mean(numbers["gaps"])),
                "routing_margin_gap": numbers["margin"]})
    return out
