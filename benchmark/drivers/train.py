"""Driver: training through ``create_train_step(cfg, mesh).step_fn``
(``tony_tpu/train/step.py``), the factory under ``examples/lm_train.py``,
on the mesh the configuration's file names. No orchestrator.

Set-up builds one object, the compiled step with its state, places the
seed's weights in it, drives it through its first steps on rows that all
differ, and hands the same object to the window. What those steps gave
(each loss, the first gradient as the optimizer's moments hold it, the
parameters' change) is compared with the plain reference once the window
has closed and the program's state is freed.
"""

from __future__ import annotations

import statistics
import threading
import time

import numpy as np

import lib

serve = lib.load("drivers/serve.py")     # the program's config and tree


def _adam_moments(opt_state):
    """The first moments of the optimizer's state, wherever the chain
    keeps them."""
    import jax

    has_mu = lambda x: hasattr(x, "mu")      # noqa: E731
    found = [s for s in jax.tree.leaves(opt_state, is_leaf=has_mu)
             if has_mu(s)]
    if len(found) != 1:
        raise RuntimeError("expected one Adam state in the optimizer chain")
    return found[0].mu


def compare(prog: dict, ref: dict) -> dict:
    """The numbers of a training cell, each by the worst leaf. Norms are
    compared by the gap between the program's and the reference's, against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger; leaves whose reference gradient is under a thousandth of the
    median leaf's are left out of the change (Adam moves them by round-off
    alone). ``grad_direction_gap`` is the distance between the two first
    gradients' sketches (``leaf_projections``) against the same norm: an
    estimate of the norm of the gradients' difference, which a lower
    precision moves where it leaves the norms in place."""
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(prog["losses"], ref["losses"]))
    g_med = statistics.median(ref["grad_norm"].values())
    grad_gap = max(abs(prog["grad_norm"][k] - r) / max(r, g_med)
                   for k, r in ref["grad_norm"].items())
    dir_gap = max(
        float(np.sqrt(np.sum(np.square(prog["grad_proj"][k] - p))))
        / max(ref["grad_norm"][k], g_med)
        for k, p in ref["grad_proj"].items())
    moved = [k for k, g in ref["grad_norm"].items() if g >= 1e-3 * g_med]
    d_med = statistics.median(ref["delta_norm"][k] for k in moved)
    delta_gap = max(
        abs(prog["delta_norm"][k] - ref["delta_norm"][k])
        / max(ref["delta_norm"][k], d_med) for k in moved)
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "grad_direction_gap": dir_gap, "param_change_gap": delta_gap}


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from tony_tpu.parallel.mesh import mesh_from_string
    from tony_tpu.train.step import create_train_step, make_optimizer

    cfg, mix, opt = ctx.cfg, ctx.mix, ctx.cfg["optimizer"]
    chips = ctx.chips
    gen = lib.load("traffic/generate.py")
    costs = lib.load("costs/dense_decoder.py")
    ref = lib.load("reference/" + cfg["reference"] + ".py")
    tcfg = serve.transformer_config(cfg, mix["seq"])
    mesh = mesh_from_string(cfg["mesh"], devices=jax.devices()[:chips])
    bundle = create_train_step(
        tcfg, mesh, optimizer=make_optimizer(
            lr=opt["lr"], weight_decay=opt["weight_decay"],
            grad_clip=opt["grad_clip"]))
    # the factory initialises weights of its own; the benchmark's are the
    # seed's, so that the reference can make them again
    for leaf in jax.tree.leaves((bundle.params, bundle.opt_state)):
        leaf.delete()
    make, key = serve.program_params(cfg, ctx.seed, jnp.float32)
    params = jax.jit(make, out_shardings=bundle.param_shardings)(key)
    opt_state = jax.jit(bundle.optimizer.init,
                        out_shardings=bundle.opt_shardings)(params)
    bundle.params = bundle.opt_state = None
    vocab, tokens_per_step = cfg["vocab_size"], mix["batch"] * mix["seq"]

    def step(i, params, opt_state):
        """The window's own call and feed: a host batch placed with the
        bundle's sharding, as a loader would, then the compiled step."""
        tok, tgt = gen.lm_batch(mix, vocab, ctx.seed, i)
        with jax.profiler.StepTraceAnnotation("bench_train_step", step_num=i):
            tok = jax.device_put(tok, bundle.tok_sharding)
            tgt = jax.device_put(tgt, bundle.tok_sharding)
            return bundle.step_fn(params, opt_state, tok, tgt)

    norms, projections = jax.jit(ref.leaf_norms), jax.jit(ref.leaf_projections)
    prog = {"losses": []}
    n_check = int(mix["check_steps"])
    for i in range(n_check):
        params, opt_state, m = step(i, params, opt_state)
        prog["losses"].append(float(m["loss"]))
        if i == 0:
            mu = _adam_moments(opt_state)      # (1 - b1) x the first gradient
            prog["grad_norm"] = {k: float(v) / (1.0 - opt["b1"])
                                 for k, v in norms(mu).items()}
            prog["grad_proj"] = {k: np.asarray(v) / (1.0 - opt["b1"])
                                 for k, v in projections(mu).items()}
            del mu
    names = ref.leaf_names(params)
    leaves = jax.tree.leaves(params)
    prog["delta_norm"] = {}
    for i, (name, leaf) in enumerate(zip(names, leaves)):
        # one leaf at a time, so that the seed's weights are never whole
        # on the device beside the state (the key is an argument: a key
        # closed over would be a constant, and every seed a new program)
        diff = jax.jit(lambda a, key, i=i: jnp.sqrt(jnp.sum(jnp.square(
            a - jax.tree.leaves(make(key))[i]))))
        prog["delta_norm"][name] = float(diff(leaf, key))
    del leaves

    watch = lib.CompileWatch.install()
    state = {"params": params, "opt_state": opt_state, "steps": 0,
             "ready": [], "t_end": None, "error": None}
    del params, opt_state

    def drive(i, until):
        """The window's loop: steps from batch ``i`` on, one step of
        runway and no more, until the clock passes ``until()``; notes
        when each step was ready."""
        p, o, prev = state["params"], state["opt_state"], None
        state["params"] = state["opt_state"] = None
        while time.monotonic() < until():
            p, o, m = step(i, p, o)
            if prev is not None:
                prev["loss"].block_until_ready()
                state["ready"].append(time.monotonic())
            prev, i = m, i + 1
            state["steps"] += 1
        prev["loss"].block_until_ready()
        state["ready"].append(time.monotonic())
        state["last_loss"] = float(prev["loss"])
        state["params"], state["opt_state"] = p, o
        return i

    # a short untimed turn of that loop after the checked steps: whatever
    # the loop itself builds on its first pass is built in set-up
    i_next = drive(n_check, lambda t=time.monotonic() + 0.5: t)
    state["steps"], state["ready"] = 0, []
    t0 = time.monotonic() + 0.05
    t1 = t0 + ctx.seconds
    c0 = watch.count

    def loop():
        try:
            time.sleep(max(0.0, t0 - time.monotonic()))
            drive(i_next, lambda: t1)
            state["t_end"] = state["ready"][-1]
            state["params"] = state["opt_state"] = None
        except Exception as e:      # read by the main thread below
            state["error"] = repr(e)

    worker = threading.Thread(target=loop, name="bench-train")
    worker.start()
    time.sleep(max(0.0, t0 - time.monotonic()))
    setup_s = time.monotonic() - ctx.t_start
    traced = ctx.trace_window(t0, t1)
    worker.join()
    if state["error"]:
        raise RuntimeError(f"the train loop failed: {state['error']}")
    memory_peak = lib.peak_memory_bytes()
    c1 = watch.count
    elapsed = state["t_end"] - t0
    rate = state["steps"] * tokens_per_step / elapsed
    del bundle
    state["params"] = state["opt_state"] = None

    flops_token = costs.train_flops_per_token(cfg, mix["seq"])
    facts = {"cfg": cfg, "window_s": elapsed, "chips": chips, "mix": mix,
             "steps": state["steps"], "tokens_per_s": rate,
             "flops": flops_token * state["steps"] * tokens_per_step,
             "programs": {"train_step": ["jit_step"]}}
    ready = [t0] + state["ready"]
    step_s = sorted(b - a for a, b in zip(ready, ready[1:]))
    notes = {"steps": state["steps"], "window_s": elapsed,
             "step_s_p50": lib.percentile(step_s, 50),
             "step_s_longest": step_s[-3:],
             "compiled_in_window": watch.names[c0:c1],
             "last_loss": state["last_loss"],
             "program": {"losses": prog["losses"]}}

    checks = lib.Checks(ctx.cell.get("limits"))
    checks.add("compiles_in_window", c1 - c0, 0)
    batches = [gen.lm_batch(mix, vocab, ctx.seed, i) for i in range(n_check)]
    want = ref.train_steps(cfg, ctx.seed, opt, batches)
    notes["reference"] = {"losses": want["losses"]}
    for name, value in compare(prog, want).items():
        checks.add(name, value)
    if ctx.control:
        low = ref.train_steps(cfg, ctx.seed, opt, batches, lowp=ctx.control)
        notes["control"] = checks.judge(compare(low, want))
        # the fault a training cell can have that no reading of the program
        # shows: half of the batch left out, the mean taken over the rest
        half = [(tok[:len(tok) // 2], tgt[:len(tgt) // 2])
                for tok, tgt in batches]
        notes["fault_half_batch"] = checks.judge(compare(
            ref.train_steps(cfg, ctx.seed, opt, half), want))
        notes["fault_state_unchanged"] = checks.judge(compare(
            ref.train_steps(cfg, ctx.seed, opt, batches, frozen=True), want))
    return {"attempted": state["steps"], "failed": 0,
            "e2e": {"train_tokens_per_s": rate}, "setup_s": setup_s,
            "facts": facts, "notes": notes, "checks": checks,
            "memory_peak_bytes": memory_peak, "traced": traced}
