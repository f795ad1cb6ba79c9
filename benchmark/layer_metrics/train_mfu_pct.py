"""The whole train step's share of the chips' peak: forward + backward
FLOPs per token (recomputation not counted) x tokens per second of the
window, over chips x peak bf16."""


def read(facts, suffix):
    if "tokens_per_s" not in facts or not facts.get("flops"):
        return None
    peak = facts["peaks"]["bf16_flops_per_s"] * facts["chips"]
    return 100.0 * facts["flops"] / (facts["window_s"] * peak)
