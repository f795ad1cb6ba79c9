"""The whole serving step's share of the chip's peak: model FLOPs of every
token processed in the window (a prompt where it was admitted, a generated
token where it was fed to its stream) over window x chips x peak bf16."""


def read(facts, suffix):
    flops = facts.get("flops")
    if not flops or "engine" not in facts:
        return None
    peak = facts["peaks"]["bf16_flops_per_s"] * facts["chips"]
    return 100.0 * flops / (facts["window_s"] * peak)
