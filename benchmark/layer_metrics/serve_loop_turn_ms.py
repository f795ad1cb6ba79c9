"""Mean distance between the starts of consecutive ``serve.step.dispatch``
spans: one turn of the serving loop while it decodes (the program's
``loop_turn_s``, read from inside and on the device trace's clock)."""
import lib


def read(facts, suffix):
    starts = [start for _, _, start, _, _ in
              lib.load("trace/host_spans.py").spans("serve.step.dispatch")]
    if len(starts) < 2:
        return None
    return (starts[-1] - starts[0]) / (len(starts) - 1) * 1e-6
