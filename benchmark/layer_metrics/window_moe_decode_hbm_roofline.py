"""The window-and-global, routed-expert decoder's decode step against the
bytes the algorithm needs: the weights no routing spares once at the
served dtype, the weights of the experts the step's live rows routed to
(``experts_touched``, the program's own count over the window), and the
K/V of the live rows' positions, all of them in each global layer and
min(length, window) in each window layer (``live_positions`` /
``window_positions`` on the ``serve.step.bookkeep`` spans, over the
blocks of the ``serve.step.sync`` spans: a processed block's last step),
by ``costs/window_moe_decoder.py``, over the chip's HBM bandwidth,
divided by the measured device time of one step (a decode block's device
time over its steps). Nothing from a program without those counts."""
import lib


def read(facts, suffix):
    names = facts.get("programs", {}).get("decode")
    counters = facts.get("counters", {})
    cfg = facts.get("cfg", {})
    steps = counters.get("blocks_dispatched", 0) \
        * facts.get("engine", {}).get("block_size", 0)
    held = counters.get("experts_held", 0)
    if not names or not steps or not held or not facts.get("decode_tokens") \
            or "sliding_window" not in cfg:
        return None
    spans = lib.load("trace/host_spans.py").spans
    blocks = sum(c.get("blocks", 0) for *_, c in spans("serve.step.sync"))
    live = window = 0
    for *_, counts in spans("serve.step.bookkeep"):
        live += counts.get("live_positions", 0)
        window += counts.get("window_positions", 0)
    if not blocks or not live:
        return None
    runs, seconds = lib.load("trace/reduce.py").program_time(
        facts["trace"], names)
    if not runs:
        return None
    costs = lib.load("costs/window_moe_decoder.py")
    # the (layer, expert) pairs a step has, times the share the window's
    # processed steps touched
    pairs = costs.experts_held(cfg) * cfg["num_hidden_layers"]
    touched = pairs * counters["experts_touched"] / held
    least = costs.decode_least_bytes(
        cfg, live / blocks, window / blocks, facts["decode_tokens"] / steps,
        touched)
    step_s = seconds / runs / facts["engine"]["block_size"]
    return 100.0 * (least / facts["peaks"]["hbm_bytes_per_s"]) / step_s
