"""Share of the slots whose recurrent state the processed decode blocks
changed: ``state_rows`` on each ``serve.step.bookkeep`` span (the device's
own count: each block reports the rows whose state differs from what the
block was given) over the slots (``serve.step.dispatch``'s ``slots``)
times the blocks those spans processed (``serve.step.sync``'s ``blocks``).
While the mask holds it equals ``decode_block_occupancy_pct`` up to the
blocks at the edges of the traced window (dispatched inside it, processed
outside, or the other way round: a few tenths of a point either way); it
reads well above it where frozen rows' states moved. Nothing from a program without the count; 0 from a model
without linear layers."""
import lib


def read(facts, suffix):
    spans = lib.load("trace/host_spans.py").spans
    rows = [c["state_rows"] for *_, c in spans("serve.step.bookkeep")
            if "state_rows" in c]
    blocks = sum(c.get("blocks", 0) for *_, c in spans("serve.step.sync"))
    slots = next((c["slots"] for *_, c in spans("serve.step.dispatch")
                  if c.get("slots")), 0)
    if not rows or not blocks or not slots:
        return None
    return 100.0 * sum(rows) / (blocks * slots)
