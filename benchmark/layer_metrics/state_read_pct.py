"""Share of the slots' recurrent states that the decode steps stream from
HBM: ``state_rows_read`` over ``state_rows_held``, the counts on each
``serve.step.bookkeep`` span (the rows whose state tiles a processed
block's last step reads and writes, by the kernel's own rule on the rows
live at that step, over the slots; 100 where the step's recurrence is
``gated_delta_step`` over every row of the layer's slice). Nothing from a
program without the counts or a model without linear layers."""
import lib


def read(facts, suffix):
    read = held = 0
    for _, _, _, _, counts in lib.load("trace/host_spans.py").spans(
            "serve.step.bookkeep"):
        read += counts.get("state_rows_read", 0)
        held += counts.get("state_rows_held", 0)
    if not held:
        return None
    return 100.0 * read / held
