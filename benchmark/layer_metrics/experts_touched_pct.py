"""Share of the held experts that the live rows of the decode steps routed
to: ``experts_touched`` over ``experts_held``, the counts on each
``serve.step.bookkeep`` span (the device's own count of the distinct
experts a step's live rows chose, a routed layer). What the scheduler's
occupancy and the router's spread leave a step to read at the least: the
floor of ``expert_weights_read_pct``."""
import lib


def read(facts, suffix):
    return lib.load("layer_metrics/expert_weights_read_pct.py").read(
        facts, suffix, count="experts_touched")
