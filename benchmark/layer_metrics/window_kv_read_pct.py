"""Share of what the window layers' decode attention would read over its
rows' whole contexts that it reads: ``window_blocks_read`` (a window
ring's blocks a processed block's last step streams, by the decode
kernel's own rule on the ring's view) over ``window_blocks_context`` (the
blocks those rows' whole contexts span), the counts on each
``serve.step.bookkeep`` span. Under 100 where contexts outgrow the window;
nothing from a program without window layers."""
import lib


def read(facts, suffix):
    read = context = 0
    for *_, counts in lib.load("trace/host_spans.py").spans(
            "serve.step.bookkeep"):
        read += counts.get("window_blocks_read", 0)
        context += counts.get("window_blocks_context", 0)
    if not context:
        return None
    return 100.0 * read / context
