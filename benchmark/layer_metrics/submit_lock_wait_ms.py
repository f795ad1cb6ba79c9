"""Mean time a caller of ``ServeApp.submit_async`` waited for
``ServeApp.lock``, over the ``serve.submit.lock_wait`` spans that ended in
the traced window (a span open at the window's end is not in the trace)."""
import lib


def read(facts, suffix):
    waits = [dur for _, _, _, dur, _ in
             lib.load("trace/host_spans.py").spans("serve.submit.lock_wait")]
    if not waits:
        return None
    return sum(waits) / len(waits) * 1e-6
