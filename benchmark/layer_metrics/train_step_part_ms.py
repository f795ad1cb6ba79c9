"""Device ms a training step spends in one part (the suffix): the
operations of the whole ``jit_step`` runs in the trace, each given the part
its instruction has in the compiled program (``mixer``, ``mlp``, ``loss``,
``optimizer``: the program's ``jax.named_scope`` names, a backward's
operations in the part of their forward, ``trace/step_parts.py``), summed
and divided by the runs; ``other`` is the runs' device time less the named
parts."""
import lib

PARTS = ("mixer", "mlp", "loss", "optimizer", "other")


def read(facts, suffix):
    if suffix not in PARTS:
        return None
    parts = lib.load("trace/step_parts.py")
    return parts.part_ms(parts.tally(facts, "train_step"), suffix, 1)
