"""Device ms a decode step spends in one part of the block (the suffix):
the operations of the whole ``_decode_block`` runs in the trace, each given
the part its instruction has in the compiled program (``mixer``, ``mlp``,
``router``, ``experts``, ``unembed``, ``counts``: the program's
``jax.named_scope`` names, ``trace/step_parts.py``), summed and divided by
runs x the engine's block size; ``other`` is the runs' device time a step
less the named parts."""
import lib

PARTS = ("mixer", "mlp", "router", "experts", "unembed", "counts", "other")


def read(facts, suffix):
    if suffix not in PARTS:
        return None
    parts = lib.load("trace/step_parts.py")
    found = parts.tally(facts, "decode")
    if found is None:
        return None
    return parts.part_ms(found, suffix, facts["engine"]["block_size"])
