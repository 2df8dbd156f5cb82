"""jaxpr/executable passes: lower the serving warmup set, verify the
compile-time contracts the engine's dynamic gates assume.

Three rules:

  * ``jit-donation`` — every executable in the serving warmup set
    (decode / chunked-prefill / spec-verify / KV segment ops, across a
    3-rung ladder) actually donates its pool caches: each donated input
    leaf must be aliased to an output in the lowered module
    (``tf.aliasing_output``), and a representative executable is
    compiled to confirm XLA honoured the aliasing
    (``input_output_alias``).  A dropped donation silently doubles the
    pool's HBM footprint and adds a full-pool copy per decode step —
    exactly what PR 1's "pool insertion donates" fix removed.
  * ``jit-static-args`` — every ``jax.jit`` signature in
    ``models/api.py`` / ``serving/engine.py`` (and the spec/pool/quality
    construction sites they feed) declares hashable, hash-stable static
    arguments: the ladder's policies must hash equal to their deep
    copies, or every equal-but-distinct policy object is a jit cache
    miss (a silent retrace — the bug class
    ``decode_retraces_after_warmup == 0`` guards at runtime, PR 3).
  * ``pallas-blockspec`` — the Pallas kernels' launch geometry
    (``kernels.sparse_matmul`` plans, ``kernels.ops.channel_plan``)
    keeps every BlockSpec index map in bounds over the whole grid, every
    tile dividing its padded dim (the PR 5 ``_fit_tile`` contract: never
    degrade below tile/2, pad instead), and the double-buffered working
    set under the per-core VMEM budget.

The passes import the model and lower real executables, so they need a
working jax install; the CLI's ``--ast-only`` skips them.
"""
from __future__ import annotations

import ast
import copy
import dataclasses
import functools
import itertools
import os
import warnings
from typing import List, Optional

from repro.analysis.findings import Finding
from repro.analysis.registry import GlobalPass, register

# the serving shapes the warmup set is lowered at — tiny on purpose
# (reduced config; lowering is tracing, not compiling)
_SLOTS = 4
_MAX_LEN = 64
_CHUNK = 16
_GAMMA = 2
_BUDGETS = (0.0, 0.5, 0.7)


def _line_of(repo_root: str, relpath: str, needle: str) -> int:
    """1-based line of the first occurrence of ``needle`` (anchor for
    findings that belong to a construction site, not a single token)."""
    try:
        with open(os.path.join(repo_root, relpath), encoding="utf-8") as fh:
            for i, line in enumerate(fh, 1):
                if needle in line:
                    return i
    except OSError:
        pass
    return 1


@functools.lru_cache(maxsize=1)
def _warmup_context():
    """Reduced model + 3-rung uniform ladder + abstract warmup inputs,
    built once per process and shared by the executable passes."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduced
    from repro.models import api
    from repro.models import params as P
    from repro.sparsity.ladder import PolicyLadder

    cfg = reduced(get_config("llama31_8b"))
    params = api.init_model(cfg, 0)
    ladder = PolicyLadder.uniform(params, cfg, budgets=_BUDGETS)
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    caches = P.abstract_params(api.cache_schema(cfg, _SLOTS, _MAX_LEN),
                               cfg.dtype)
    sds = jax.ShapeDtypeStruct
    i32, f32 = jnp.dtype("int32"), jnp.dtype("float32")
    shapes = {
        "tokens": sds((_SLOTS,), i32),
        "positions": sds((_SLOTS,), i32),
        "active": sds((_SLOTS,), f32),
        "chunk_tokens": sds((1, _CHUNK), i32),
        "chunk_offset": sds((1,), i32),
        "chunk_slot": sds((), i32),
        "chunk_weights": sds((_CHUNK,), f32),
        "verify_tokens": sds((_SLOTS, _GAMMA + 1), i32),
        "verify_weights": sds((_SLOTS, _GAMMA + 1), f32),
    }
    phases = [(pol.for_phase("prefill_dense"), pol.for_phase("prefill_sparse"),
               pol.for_phase("decode")) for pol in ladder.policies]
    sp_abs = [
        None if sp is None else jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), sp)
        for sp in ladder.sps
    ]
    return cfg, params, ladder, abstract, caches, shapes, phases, sp_abs


def _count_leaves(tree) -> int:
    import jax
    return len(jax.tree_util.tree_leaves(tree))


def _lowered_alias_count(lowered) -> int:
    return lowered.as_text().count("tf.aliasing_output")


def _compiled_alias_count(compiled) -> int:
    text = compiled.as_text()
    return text.count("may-alias") + text.count("must-alias")


@register
class JitDonationPass(GlobalPass):
    """Donation actually takes for the full serving warmup executable set.

    For each of the 3 uniform-ladder rungs this lowers the decode and
    both prefill-chunk phase executables (plus the spec-verify
    executable at the verifier rung and the KV pool's donated segment
    ops) through the SAME construction sites the engine uses
    (``engine.make_engine_steps``, ``spec.make_verify_jit``,
    ``SlotKVPool``), then requires one ``tf.aliasing_output`` annotation
    per donated cache leaf.  Motivated by PR 1's pool-copy fix and PR
    4's rollback donation; dynamic counterpart:
    ``tests/test_perf_paths.py``.
    """

    rule = "jit-donation"

    def run(self, repo_root: str) -> List[Finding]:
        from repro.serving.engine import make_engine_steps
        from repro.serving.spec import make_verify_jit

        cfg, params, ladder, abstract, caches, shapes, phases, sp_abs = \
            _warmup_context()
        findings: List[Finding] = []
        engine_rel = "src/repro/serving/engine.py"
        engine_line = _line_of(repo_root, engine_rel, "donate_argnums=(3,)")
        n_cache = _count_leaves(caches)

        dstep, cstep, _pstep = make_engine_steps(cfg)
        lowered = {}
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            for r, ((pd, ps, dec), sp) in enumerate(zip(phases, sp_abs)):
                lowered[f"decode[rung={r}]"] = dstep.lower(
                    abstract, shapes["tokens"], shapes["positions"], caches,
                    sp, shapes["active"], policy=dec)
                for name, pol in (("prefill_dense", pd),
                                  ("prefill_sparse", ps)):
                    lowered[f"chunk[rung={r},{name}]"] = cstep.lower(
                        abstract, shapes["chunk_tokens"],
                        shapes["chunk_offset"], shapes["chunk_slot"], caches,
                        sp, shapes["chunk_weights"], policy=pol)
            vstep = make_verify_jit(cfg)
            _, _, dec0 = phases[0]
            lowered[f"verify[gamma={_GAMMA}]"] = vstep.lower(
                abstract, shapes["verify_tokens"], shapes["positions"],
                caches, sp_abs[0], shapes["verify_weights"], policy=dec0)

        for name, lo in lowered.items():
            got = _lowered_alias_count(lo)
            if got != n_cache:
                findings.append(Finding(
                    rule=self.rule, path=engine_rel, line=engine_line,
                    message=(f"{name}: donation dropped — {got} of "
                             f"{n_cache} donated cache leaves are aliased "
                             "to outputs in the lowered module; the pool "
                             "would be copied every step"),
                    snippet=name))

        # segment executables: the pool's donated write/rollback ops
        findings.extend(self._check_pool(repo_root, cfg))

        # compile one representative executable end-to-end: XLA must
        # honour the aliasing, not just receive the request
        _, _, dec1 = phases[1]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            compiled = dstep.lower(
                abstract, shapes["tokens"], shapes["positions"], caches,
                sp_abs[1], shapes["active"], policy=dec1).compile()
        got = _compiled_alias_count(compiled)
        if got < n_cache:
            findings.append(Finding(
                rule=self.rule, path=engine_rel, line=engine_line,
                message=(f"decode[rung=1] compiled: XLA honoured only "
                         f"{got} of {n_cache} requested cache aliases "
                         "(input_output_alias) — donation requested but "
                         "not taken on this backend"),
                snippet="decode[rung=1] input_output_alias"))
        return findings

    def _check_pool(self, repo_root: str, cfg) -> List[Finding]:
        import jax
        import jax.numpy as jnp

        from repro.models import api
        from repro.models import params as P
        from repro.serving.kv_pool import SlotKVPool

        findings: List[Finding] = []
        rel = "src/repro/serving/kv_pool.py"
        pool = SlotKVPool(cfg, _SLOTS, _MAX_LEN)
        caches_abs = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), pool.caches)
        n_cache = _count_leaves(caches_abs)
        seg_abs = P.abstract_params(
            api.prefix_segment_schema(cfg, _CHUNK), cfg.dtype)
        sds = jax.ShapeDtypeStruct
        i32 = jnp.dtype("int32")
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            cases = {
                "segment-write": (pool._write_jit, "donate_argnums=(0,)",
                                  (caches_abs, seg_abs, sds((), i32))),
                "rollback": (pool._rollback_jit, "donate_argnums=(0,)",
                             (caches_abs, sds((_SLOTS,), i32),
                              sds((_SLOTS,), i32))),
            }
            for name, (jitted, needle, args) in cases.items():
                got = _lowered_alias_count(jitted.lower(*args))
                if got != n_cache:
                    findings.append(Finding(
                        rule=self.rule, path=rel,
                        line=_line_of(repo_root, rel, needle),
                        message=(f"{name}: donation dropped — {got} of "
                                 f"{n_cache} donated pool leaves aliased"),
                        snippet=name))
        return findings


@register
class JitStaticArgsPass(GlobalPass):
    """Static-argnum hashability and stability of every jitted signature.

    Enumerates ``jax.jit`` call sites in ``models/api.py``,
    ``serving/engine.py``, ``serving/spec.py``, ``serving/kv_pool.py``
    and ``obs/quality.py`` via AST; requires each to declare its statics
    explicitly (``static_argnames``/``static_argnums``) when it takes a
    policy, and dynamically verifies the warmup set's policies are
    frozen, hashable and hash-stable under deep copy — an
    identity-hashed (or mutable) policy turns every call into a retrace
    (PR 2 made SparsityPolicy frozen/hashable for exactly this;
    dynamic counterpart: the zero-retrace gates in
    ``tests/test_serving.py`` / ``tests/test_ladder.py``).
    """

    rule = "jit-static-args"
    _FILES = (
        "src/repro/models/api.py",
        "src/repro/serving/engine.py",
        "src/repro/serving/spec.py",
        "src/repro/serving/kv_pool.py",
        "src/repro/obs/quality.py",
    )

    def run(self, repo_root: str) -> List[Finding]:
        findings: List[Finding] = []
        jit_sites = []          # (relpath, line, statics: set[str]|None)
        for rel in self._FILES:
            path = os.path.join(repo_root, rel)
            if not os.path.exists(path):
                continue
            with open(path, encoding="utf-8") as fh:
                tree = ast.parse(fh.read(), filename=rel)
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "jit"
                        and isinstance(node.func.value, ast.Name)
                        and node.func.value.id == "jax"):
                    continue
                statics = None
                for kw in node.keywords:
                    if kw.arg in ("static_argnames", "static_argnums"):
                        statics = kw
                jit_sites.append((rel, node.lineno, statics))

        if not jit_sites:
            findings.append(Finding(
                rule=self.rule, path=self._FILES[0], line=1,
                message=("found no jax.jit sites in the serving/model "
                         "files — the static-args audit has lost track "
                         "of where executables are built; update "
                         "JitStaticArgsPass._FILES"),
                snippet="no jit sites"))
            return findings

        # the values actually used as statics in the warmup set
        _, _, ladder, *_rest, phases, _sp = _warmup_context()
        policies = {p for tup in phases for p in tup}
        policies.update(ladder.policies)
        for pol in policies:
            findings.extend(self._check_policy(pol, jit_sites))
        return findings

    def _check_policy(self, pol, jit_sites) -> List[Finding]:
        site = next(((rel, line) for rel, line, statics in jit_sites
                     if statics is not None), jit_sites[0][:2])
        rel, line = site
        out: List[Finding] = []

        def finding(msg):
            return Finding(rule=self.rule, path=rel, line=line,
                           message=msg, snippet=f"policy {pol!r:.60}")

        if not (dataclasses.is_dataclass(pol)
                and pol.__dataclass_params__.frozen):
            out.append(finding(
                f"static policy {type(pol).__name__} is not a frozen "
                "dataclass — mutable statics can change under a cached "
                "executable's feet"))
        try:
            # in-process jit cache key stability; cross-process hash
            # stability is NOT required (executables are not persisted),
            # so builtin hash() is exactly right here — this IS the
            # hashability check the rule exists to protect.
            h0 = hash(pol)  # repro: ignore[no-builtin-hash-persistence]
            h1 = hash(copy.deepcopy(pol))  # repro: ignore[no-builtin-hash-persistence]
        except TypeError as e:
            out.append(finding(
                f"static policy is unhashable ({e}) — jit would raise "
                "at every call site declaring it static"))
            return out
        if h0 != h1 or pol != copy.deepcopy(pol):
            out.append(finding(
                "static policy hash/eq is identity-based: a deep copy "
                "hashes differently, so every equal-but-distinct policy "
                "object is a fresh trace (silent retrace per call)"))
        return out


def tiling_violation(block) -> Optional[str]:
    """The TPU block-shape rule for one ``BlockPlan``: each of the
    block's last two dims must be a multiple of (SUBLANE, LANE) — LANE
    alone for a 1-D block — or span the whole array dim.  Returns what
    is wrong, or None."""
    from repro.kernels import sparse_matmul as K
    dims, padded = block.dims, block.padded
    aligns = (K.SUBLANE, K.LANE)[-len(dims):]
    for d, pad, align in zip(dims[-2:], padded[-2:], aligns):
        if d % align and d != pad:
            return (f"block {block.block} over array {padded}: dim {d} "
                    f"is neither a multiple of {align} nor the array's "
                    f"{pad}")
    return None


@register
class PallasBlockSpecPass(GlobalPass):
    """Pallas kernel launch contracts: index maps in bounds, tiles
    divide padded dims, every block meets the TPU tiling rule (last two
    dims divisible by (8, 128) or equal to the array's), VMEM working
    set under budget.

    Sweeps the kernel plans (``kernels.sparse_matmul.shared_plan`` /
    ``per_seq_plan`` / ``score_mask_plan`` — the same objects the
    kernels launch from) over representative serving shapes including
    the prime/awkward dims from PR 5's ``_fit_tile`` fix, evaluating
    every BlockSpec index map across the full grid with worst-case
    kept-block ids.  Motivated by the PR 5 tile-collapse bug (1-wide
    tiles on prime dims); dynamic counterpart: the awkward-shape
    regression tests in ``tests/test_kernels.py``.
    """

    rule = "pallas-blockspec"
    _REL = "src/repro/kernels/sparse_matmul.py"

    # (B, n_channels, m_out): production-ish plus the prime/awkward dims
    _SHAPES = (
        (8, 4096, 4096),
        (8, 4096, 11008),
        (1, 5120, 13824),
        (3, 2048, 311),      # prime output dim -> pad path
        (7, 384, 640),       # prime batch
        (5, 256, 509),       # prime output under tile/2
        (1, 128, 128),
    )
    # W's leading dim: a 2-D weight (1) and a layer stack the kernel
    # reads in place
    _LAYERS = (1, 6)

    def run(self, repo_root: str) -> List[Finding]:
        import numpy as np

        from repro.kernels import ops
        from repro.kernels import sparse_matmul as K

        findings: List[Finding] = []

        def check_plan(plan, prefetch_values, line_needle):
            line = _line_of(repo_root, self._REL, line_needle)
            for dim, tile, padded in plan.tiles:
                if tile < 1 or padded % tile:
                    findings.append(Finding(
                        rule=self.rule, path=self._REL, line=line,
                        message=(f"{plan.kernel}: tile {tile} does not "
                                 f"divide padded dim {dim}={padded} — the "
                                 "_fit_tile contract (divisor in "
                                 "[tile/2, tile] or pad to a multiple) "
                                 "is broken"),
                        snippet=f"{plan.kernel} tiles {plan.tiles}"))
            for b in plan.blocks:
                bad = tiling_violation(b)
                if bad:
                    findings.append(Finding(
                        rule=self.rule, path=self._REL, line=line,
                        message=(f"{plan.kernel}: operand {b.name} {bad} "
                                 "— Mosaic refuses the launch (interpret "
                                 "mode hides it)"),
                        snippet=f"{plan.kernel}/{b.name} tiling"))
            if plan.vmem_bytes() > K.VMEM_BYTES:
                findings.append(Finding(
                    rule=self.rule, path=self._REL, line=line,
                    message=(f"{plan.kernel}: double-buffered working set "
                             f"{plan.vmem_bytes()} B exceeds the "
                             f"{K.VMEM_BYTES} B per-core VMEM budget for "
                             f"grid {plan.grid}"),
                    snippet=f"{plan.kernel} vmem {plan.vmem_bytes()}"))
            grid_points = itertools.product(*(range(g) for g in plan.grid))
            if np.prod(plan.grid) > 8192:
                corners = [(0, g // 2, g - 1) for g in plan.grid]
                grid_points = itertools.product(*corners)
            for point in grid_points:
                for prefetch in prefetch_values:
                    for b in plan.blocks:
                        origin = b.index_map(*point, *prefetch)
                        for d, (o, blk_d, pad_d) in enumerate(
                                zip(origin, b.dims, b.padded)):
                            if o < 0 or (int(o) + 1) * blk_d > pad_d:
                                findings.append(Finding(
                                    rule=self.rule, path=self._REL,
                                    line=line,
                                    message=(
                                        f"{plan.kernel}: operand "
                                        f"{b.name} index map out of "
                                        f"bounds at grid {point} dim {d}: "
                                        f"block origin {int(o)} x "
                                        f"{blk_d} exceeds padded dim "
                                        f"{pad_d}"),
                                    snippet=f"{plan.kernel}/{b.name}"))
                                return      # one finding per plan is enough

        for B, n, m in self._SHAPES:
            blk = min(K.DEFAULT_BLK, n)
            nb_pad = (n + (-n % blk)) // blk
            for kb, L in itertools.product({1, max(1, nb_pad // 2), nb_pad},
                                           self._LAYERS):
                # worst-case kept-block ids at the stack's first and last
                # layer (the second scalar-prefetch operand)
                lyrs = [np.zeros(1, np.int32), np.full(1, L - 1, np.int32)]
                plan = K.shared_plan(B, n + (-n % blk), m, kb, L=L)
                idxs = [np.zeros(kb, np.int32),
                        np.full(kb, nb_pad - 1, np.int32)]
                check_plan(plan, list(zip(idxs, lyrs)), "def shared_plan")
                plan = K.per_seq_plan(B, n + (-n % blk), m, kb, L=L)
                idxs = [np.zeros((B, kb), np.int32),
                        np.full((B, kb), nb_pad - 1, np.int32)]
                check_plan(plan, list(zip(idxs, lyrs)), "def per_seq_plan")
            sm = K.score_mask_plan(B, n + (-n % blk))
            check_plan(sm, [(np.zeros(2, np.float32),)],
                       "def score_mask_plan")

        # channel_plan contract: full-width blocks via padding, never
        # 1-wide fallback (the ops.wisparse_project side of PR 5's fix)
        ops_rel = "src/repro/kernels/ops.py"
        ops_line = _line_of(repo_root, ops_rel, "def channel_plan")
        for n in (128, 256, 311, 384, 509, 4096, 64, 1):
            blk, n_padded, nb = ops.channel_plan(n)
            if n_padded % blk or n_padded < n or n_padded - n >= blk \
                    or nb != n_padded // blk or blk != min(128, n):
                findings.append(Finding(
                    rule=self.rule, path=ops_rel, line=ops_line,
                    message=(f"channel_plan(n={n}) broke the padded "
                             f"full-width-block contract: blk={blk}, "
                             f"n_padded={n_padded}, nb={nb}"),
                    snippet=f"channel_plan({n})"))

        # _fit_tile postconditions over a dense sweep: result divides the
        # dim (or signals the pad path by returning `want` verbatim),
        # never degrades below want/2, and is aligned or the whole dim
        fit_line = _line_of(repo_root, self._REL, "def _fit_tile")
        for size in range(1, 600):
            for want, align in ((8, K.SUBLANE), (128, K.LANE),
                                (256, K.LANE)):
                t = K._fit_tile(size, want, align)
                eff_want = min(want, size)
                ok = (1 <= t <= eff_want and 2 * t >= eff_want
                      and (size % t == 0 or t == eff_want)
                      and (t % align == 0 or t == size))
                if not ok:
                    findings.append(Finding(
                        rule=self.rule, path=self._REL, line=fit_line,
                        message=(f"_fit_tile({size}, {want}, {align}) = {t} "
                                 "breaks the contract: the whole dim, an "
                                 "aligned divisor in [want/2, want], or "
                                 "want (pad path)"),
                        snippet=f"_fit_tile({size},{want})={t}"))
        return findings
