"""expert_sparse_mm_roofline (layer: kernels, kernels/sparse_matmul.py
under models/moe.py's per-expert vmap): the least time the traced
decode steps' block-sparse expert projections need -- the kept weight
blocks of every published expert expected to be routed to, read once,
the kept part of x read and y written per routed assignment, 2 FLOPs per
kept multiply-add, over the rows that got a token (bench/counts.py) --
over the summed device time of the expert kernel's events inside
``repro/decode`` annotations, in %.  None on a shape without experts."""
from bench import counts, trace

# JAX batches a Pallas call whose scalar-prefetch operand (here each
# expert's kept block ids) is mapped as a loop of one call per expert, so
# an expert's kernel is the sparse-matmul custom call (an f32 result from
# an int32 block-index vector first) on that expert's own weight, which
# reaches the kernel as a stack of one layer: ``%closed_call.9 =
# f32[96,512]{...} custom-call(s32[6]{0} %a, s32[1]{0} %b,
# bf16[96,1536]{...} %c, bf16[1,1536,512]{...} %d)``.  The attention
# projections' calls read their weight in place from the stack of all
# layers (``bf16[16,1536,512]``), which may have the same last two dims
# as an expert's weight, so the layer stack's shape is left out.
CALL = r"= f32\[[\d,]+\]\{[^}]*\} custom-call\(s32\[[\d,]+\]"


def kernel_pattern(s: counts.Shape) -> str:
    """The expert kernel's calls for a configuration's shape: the call
    with a weight operand whose last two dims are an expert projection's
    (n, m), that is not the ``(layers, n, m)`` stack of attention."""
    dims = sorted({(n, m) for role, n, m in counts.projections(s)
                   if role.startswith("mlp/")})
    weights = "|".join(
        rf"(?!{s.layers},{n},{m}\])(?:\d+,)+{n},{m}\]" for n, m in dims)
    return rf"{CALL}.*\b\w+\[(?:{weights})"


def read(run):
    keep = run.cell.rung.get("keep_frac")
    if run.trace is None or not run.trace.devices or keep is None \
            or not run.shape.experts:
        return None
    steps = [run.client.steps[i] for i in run.traced_steps]
    work = None
    for s in steps:
        if s.action != "decode":
            continue
        w = counts.expert_sparse_matmul_work(run.shape, s.rows, float(keep))
        work = w if work is None else work + w
    ns, n = trace.kernel_ns(run.trace, kernel_pattern(run.shape),
                            "repro/decode", run.trace_lo, run.trace_hi)
    if work is None or n == 0 or ns <= 0:
        return None
    least, _bound = work.least_s(counts.peaks(run.device_kind))
    return 100.0 * least / (ns * 1e-9)

