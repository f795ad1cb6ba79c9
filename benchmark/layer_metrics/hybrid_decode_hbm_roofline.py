"""The hybrid decoder's decode step against the bytes the algorithm needs:
every weight once at the served dtype, K and V of the live positions of the
full-attention layers, and the recurrent state and convolution tail of the
live slots read and written once (``costs/hybrid_decoder.py``), over the
chip's HBM bandwidth, divided by the measured device time of one step (a
decode block's device time over its steps). Whatever implements the step,
the least bytes are the same."""
import lib


def read(facts, suffix):
    names = facts.get("programs", {}).get("decode")
    steps = facts.get("counters", {}).get("blocks_dispatched", 0) \
        * facts.get("engine", {}).get("block_size", 0)
    if not names or not steps or not facts.get("decode_tokens") \
            or "layer_types" not in facts.get("cfg", {}):
        return None
    runs, seconds = lib.load("trace/reduce.py").program_time(
        facts["trace"], names)
    if not runs:
        return None
    costs = lib.load("costs/hybrid_decoder.py")
    # a fed token is one live slot for one step, at its context
    least = costs.decode_least_bytes(
        facts["cfg"], facts["decode_context_sum"] / steps,
        facts["decode_tokens"] / steps)
    step_s = seconds / runs / facts["engine"]["block_size"]
    return 100.0 * (least / facts["peaks"]["hbm_bytes_per_s"]) / step_s
