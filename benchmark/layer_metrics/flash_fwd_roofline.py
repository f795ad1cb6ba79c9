"""The flash forward kernel against its roofline: the least time the chip
could take for one step's calls (a layer each; the larger of FLOPs over
peak and bytes over bandwidth, both from the shapes) over the device time
of the kernel's events in a whole step of the trace. The kernels are found
by the name of the function that calls them (``ops/attention.py``
``_flash_fwd``), whichever Pallas kernel it picks."""
import lib

KERNELS = ("_flash_fwd",)


def read(facts, suffix, kernels=KERNELS, backward=False):
    mix, cfg, trace = facts.get("mix"), facts["cfg"], facts["trace"]
    if not mix or mix.get("kind") != "lm_batches" or not trace["steps"]:
        return None
    runs, seconds = lib.load("trace/reduce.py").op_time(
        trace, kernels, "step_ops")
    if not runs:
        return None
    costs = lib.load("costs/dense_decoder.py")
    rows = mix["batch"] // facts["chips"] or 1
    shape = (rows, cfg["num_attention_heads"], mix["seq"], cfg["head_dim"])
    peaks = facts["peaks"]
    least = max(costs.flash_flops(*shape, backward) / peaks["bf16_flops_per_s"],
                costs.flash_bytes(*shape, backward) / peaks["hbm_bytes_per_s"])
    return 100.0 * least * cfg["num_hidden_layers"] / (seconds / trace["steps"])
