"""Kernels (layers/moe.py expert kernel, kernels/experts.py): the least
time the chip needs for the held experts' grouped matmuls (the
architecture's ``expert_kernel_cost``, per MoE layer) over the device time
of the kernel the trace names ``expert_gmm`` and of the copies that stage
its weights, across the traced decode ticks.

XLA hands the kernel each layer's expert weights as copies cut out of the
layer-stacked parameters: fusions named ``dynamic-slice_bitcast_fusion``
copy the gate, up and down weights (to HBM or VMEM), and the gate's then
reaches VMEM in async slices whose waits are named ``slice-done``. In the
Moonlight decode step those are all such ops but four 3 us waits a layer.
The weights the count reads from HBM are read there, so that time is the
kernel's too; the kernel alone reads 118% with the count's bytes
(PERF.md). An architecture without experts has no expert kernel and
gives it no work: 0."""
from bench import costs, trace_reduce

KERNEL = "expert_gmm"
STAGING = ("dynamic-slice_bitcast_fusion", "slice-done")


def read(run):
    cost = getattr(run.cell.arch, "expert_kernel_cost", None)
    if cost is None:
        return 0.0
    moe_layers = run.sz["layers"] - run.sz["dense_layers"]
    need = spent = kernel = 0.0
    for t, (a, b) in run.traced_ticks:
        if t["kind"] != "decode":
            continue
        for name, _, d in trace_reduce.ops_in(run.trace["ops"], a, b):
            kind = trace_reduce.op_kind(name)
            if KERNEL in kind:
                kernel += d / 1e9
            if KERNEL in kind or kind.startswith(STAGING):
                spent += d / 1e9
        need += moe_layers * costs.roofline_seconds(*cost(run.sz, t["rows"]),
                                                    run.peak)
    return 100.0 * need / spent if kernel else None
