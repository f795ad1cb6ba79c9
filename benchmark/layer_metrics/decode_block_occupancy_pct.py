"""Mean share of the slots that a decode block decodes for: ``live`` over
``slots``, the counts on each ``serve.step.dispatch`` span."""
import lib


def read(facts, suffix):
    shares = [counts["live"] / counts["slots"] for _, _, _, _, counts in
              lib.load("trace/host_spans.py").spans("serve.step.dispatch")
              if counts.get("slots")]
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
