"""Device ms a decode step spends in the hyper-connections: the operations
of the whole ``_decode_block`` runs in the trace that the compiled program
gives the part ``hyper`` (their coefficients, the Sinkhorn projection, the
pre-mix and the stream mix at each of a layer's two sites, the expansion
at the embedding and the collapse before the final norm:
``trace/step_parts.py``), summed and divided by runs x the engine's block
size. None where the program names no such part (a program without
hyper-connections)."""
import lib


def read(facts, suffix):
    parts = lib.load("trace/step_parts.py")
    return parts.part_ms(parts.tally(facts, "decode"), "hyper",
                         facts["engine"]["block_size"])
