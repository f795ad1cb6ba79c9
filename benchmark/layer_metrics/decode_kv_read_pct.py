"""Share of the slots' KV blocks that the decode steps stream from HBM:
``kv_blocks_read`` over ``kv_blocks_ring``, the counts on each
``serve.step.bookkeep`` span (100 where the step's attention is the einsum
over the whole ring; nothing from a program without the counts)."""
import lib


def read(facts, suffix):
    read = ring = 0
    for _, _, _, _, counts in lib.load("trace/host_spans.py").spans(
            "serve.step.bookkeep"):
        read += counts.get("kv_blocks_read", 0)
        ring += counts.get("kv_blocks_ring", 0)
    if not ring:
        return None
    return 100.0 * read / ring
