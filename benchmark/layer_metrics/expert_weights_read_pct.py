"""Share of the held experts' weights that the decode steps stream from
HBM: ``experts_read`` over ``experts_held``, the counts on each
``serve.step.bookkeep`` span (the (layer, expert) pairs some row of a step
routed to, an idle row's stale token included: those the grouped matmul
visits; over the pairs held times the steps). Its floor is
``experts_touched_pct``. Nothing from a program without the counts or a
model without routed layers."""
import lib


def read(facts, suffix, count="experts_read"):
    read = held = 0
    for *_, counts in lib.load("trace/host_spans.py").spans(
            "serve.step.bookkeep"):
        read += counts.get(count, 0)
        held += counts.get("experts_held", 0)
    if not held:
        return None
    return 100.0 * read / held
