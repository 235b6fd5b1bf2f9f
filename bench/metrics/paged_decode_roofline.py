"""paged_decode_roofline (%), kernel: least time of the traced decode
steps' paged attention over the kernel's device time in them. Least time
per call is the larger of its operations over the bf16 peak and its bytes
over HBM bandwidth, for the tokens resident in each sequence
(harness/flops.py), whatever the kernel reads."""

from harness import flops
from harness import trace as trace_lib


def kernel_pattern(config) -> str:
    """The paged attention kernel among the TPU custom calls (Pallas
    kernels) of a step. The program gives its ``pallas_call`` no name, so
    the trace names it only by an HLO name that changes with the program
    around it; its result shape is fixed by what it computes: one row of
    head_dim per query head of each of the ``max_batch`` sequences,
    ``[rows, heads per row, head_dim]`` with rows x heads = max_batch x
    num_attention_heads, in whatever packing the tuned config chose. A
    kernel of another result shape is not counted."""
    n = config["deployment"]["max_batch"] * config["num_attention_heads"]
    dh = config["hidden_size"] // config["num_attention_heads"]
    rows = "|".join(f"{n // g},{g}" for g in range(1, n + 1) if n % g == 0)
    return rf"= \w+\[(?:{rows}),{dh}\] custom-call:tpu_custom_call$"


def read(run):
    pairs = run.traced_steps()
    red = run.reduce(kernels={"paged_decode": kernel_pattern(run.config)})
    if not pairs or not red:
        return None
    layers = run.config["num_hidden_layers"]
    least = spent = 0.0
    for (step, span), t in zip(pairs, trace_lib.kernel_time_in(
            red, "paged_decode", [sp for _, sp in pairs])):
        if not step.decode_ctx or t <= 0:
            continue
        c = flops.paged_decode_cost(run.config, step.decode_ctx)
        least += layers * max(c["flops"] / run.peaks["bf16_flops_per_s"],
                              c["bytes"] / run.peaks["hbm_bytes_per_s"])
        spent += t
    return 100.0 * least / spent if spent > 0 else None
