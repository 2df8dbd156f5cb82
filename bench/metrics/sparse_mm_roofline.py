"""sparse_mm_roofline (layer: kernels, kernels/sparse_matmul.py): the
least time the traced decode steps' block-sparse projections need --
kept weight blocks read once, the kept part of x read, y written, 2
FLOPs per kept multiply-add, over the rows that got a token
(bench/counts.py) -- over the summed device time of the sparse-matmul
kernel's events inside ``repro/decode`` annotations, in %.  Decode at
these batches is bound by bytes."""
from bench import counts, trace

# The Pallas kernel of sparse_matmul_shared.  The TPU trace names an op
# by its HLO text, where a Pallas kernel is an anonymous custom call; this
# one is the custom call that takes the kept blocks' int32 indices first
# and returns an f32 (rows, out) matrix: ``%closed_call.61 =
# f32[32,8192]{...} custom-call(s32[32]{...} %idx, bf16[32,8192]{...} %x,
# bf16[8192,8192]{...} %w)``.  The score/mask kernel returns a tuple.
KERNEL = r"= f32\[\d+,\d+\]\{[^}]*\} custom-call\(s32\[\d+\]"


def read(run):
    keep = run.cell.rung.get("keep_frac")
    if run.trace is None or not run.trace.devices or keep is None:
        return None
    steps = [run.client.steps[i] for i in run.traced_steps]
    steps = [s for s in steps if s.action == "decode"]
    work = None
    for s in steps:
        w = counts.sparse_matmul_work(run.shape, s.rows, float(keep))
        if w is None:
            return None
        work = w if work is None else work + w
    ns, n = trace.kernel_ns(run.trace, KERNEL, "repro/decode",
                            run.trace_lo, run.trace_hi)
    if work is None or n == 0 or ns <= 0:
        return None
    least, _bound = work.least_s(counts.peaks(run.device_kind))
    return 100.0 * least / (ns * 1e-9)
