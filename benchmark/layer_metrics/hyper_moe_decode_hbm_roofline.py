"""A routed-expert decoder's decode step against the bytes the algorithm
needs, from the cost arithmetic its configuration names
(``costs/<reference>.py``): the weights no routing spares once at the
served dtype, the weights of the experts the step's live rows routed to
(``experts_touched``, the program's own count over the window), the cached
rows of the live positions and, with hyper-connections, their float32
weights once; over the chip's HBM bandwidth, divided by the measured
device time of one step (a decode block's device time over its steps).
Nothing for a configuration without hyper-connections: the routed
configurations without them keep ``moe_decode_hbm_roofline``, the same
quotient, until one reader serves them all."""
import lib


def read(facts, suffix):
    names = facts.get("programs", {}).get("decode")
    counters = facts.get("counters", {})
    cfg = facts.get("cfg", {})
    steps = counters.get("blocks_dispatched", 0) \
        * facts.get("engine", {}).get("block_size", 0)
    held = counters.get("experts_held", 0)
    if not names or not steps or not held or not facts.get("decode_tokens") \
            or not cfg.get("hc_mult"):
        return None
    runs, seconds = lib.load("trace/reduce.py").program_time(
        facts["trace"], names)
    if not runs:
        return None
    costs = lib.load("costs/" + cfg["reference"] + ".py")
    # the (layer, expert) pairs a step has, times the share the window's
    # processed steps touched
    pairs = costs.experts_held(cfg) * costs.n_layers(cfg, "routed")
    touched = pairs * counters["experts_touched"] / held
    # a fed token is one live row for one step, at its context
    least = costs.decode_least_bytes(
        cfg, facts["decode_context_sum"] / steps,
        facts["decode_tokens"] / steps, touched)
    step_s = seconds / runs / facts["engine"]["block_size"]
    return 100.0 * (least / facts["peaks"]["hbm_bytes_per_s"]) / step_s
