"""A cell's files, found by name, and the program built from them.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration is ``bench/configs/<config>.json``, its traffic
``bench/traffic/<traffic>.json`` and its rung (the sparsity policy it
serves) ``bench/rungs/<rung>.json``, named by the configuration's
``rung`` key.  A pair of configuration and traffic forms one cell only,
so a model served on two rungs under one mix has a configuration file
for each rung.  Adding a cell adds files and an entry; nothing here
names a cell.
"""
from __future__ import annotations

import dataclasses
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")


def _load(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict
    cfg: dict
    traffic: dict
    rung: dict
    limits: dict

    @property
    def serving(self) -> dict:
        return self.cfg["serving"]


def find(name: str, rehearse: bool = False) -> Cell:
    """The cell named ``name`` in ``BENCHMARK.json`` with its files;
    ``rehearse`` shrinks the configuration to a CPU-sized stand-in."""
    entries = {w["name"]: w for w in benchmark()["workloads"]}
    if name not in entries:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have: {sorted(entries)})")
    e = entries[name]
    cfg = _load("configs", e["config"] + ".json")
    limits_path = os.path.join(BENCH, "limits", name + ".json")
    limits = _load("limits", name + ".json") if os.path.exists(limits_path) \
        else {}
    cell = Cell(name, e, cfg, _load("traffic", e["traffic"] + ".json"),
                _load("rungs", cfg["rung"] + ".json"), limits)
    if rehearse:
        cell.cfg = shrink(cfg)
        cell.traffic = shrink_traffic(cell.traffic, cell.cfg["serving"])
    return cell


def shrink(cfg: dict) -> dict:
    """A float32 stand-in of ``cfg`` small enough for a CPU: two layers,
    tiny widths, a short pool; the same model type and rung paths."""
    c = dict(cfg)
    c.update(hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
             head_dim=64, intermediate_size=256, vocab_size=4096,
             num_hidden_layers=2, torch_dtype="float32")
    if "num_local_experts" in c:
        c.update(num_local_experts=4, num_experts_per_tok=2,
                 capacity_factor=2.0)
    c["serving"] = {"max_slots": 4, "max_len": 160, "prefill_chunk": 32}
    return c


def shrink_traffic(t: dict, serving: dict) -> dict:
    t = dict(t)
    cap = serving["max_len"] - 1
    t["prompt"] = dict(t["prompt"], median=24, min=8, max=64)
    t["output"] = dict(t["output"], median=32, min=4, max=96)
    t["max_total"] = min(t["max_total"], cap)
    if t["loop"] == "closed":
        t["clients"] = serving["max_slots"]
    else:
        t["rate"] = 4.0
        t["lead_s"] = 1.0
    return t


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    program's own architecture entry with every size set from the file."""
    import dataclasses as dc

    from repro.configs import get_config
    base = get_config(cfg["program"]["arch"])
    heads = cfg["num_attention_heads"]
    kw = dict(num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
              num_heads=heads, num_kv_heads=cfg["num_key_value_heads"],
              head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
              d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
              tie_embeddings=bool(cfg["tie_word_embeddings"]),
              rope_theta=float(cfg["rope_theta"]),
              norm_eps=float(cfg["rms_norm_eps"]),
              dtype=cfg["torch_dtype"])
    if "num_local_experts" in cfg:
        kw.update(num_experts=cfg["num_local_experts"],
                  num_experts_per_tok=cfg["num_experts_per_tok"],
                  moe_d_ff=cfg["intermediate_size"],
                  capacity_factor=float(cfg["capacity_factor"]))
    return dc.replace(base, **kw)


def _leaf_name(path) -> str:
    """'groups/0/l0/attn/wq' -> 'attn/wq'; 'embed' -> 'embed'."""
    keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
    if keys[0] == "groups":
        return "/".join(keys[3:])
    return "/".join(keys)


def param_builder(pcfg, cfg: dict, seed: int):
    """(build, treedef): ``build()`` returns the leaves of the program's
    parameter tree drawn from the seed (``bench.weights``) in the served
    dtype.  The program's layout is read from its abstract model and
    every leaf's layer and expert counts are checked against the
    configuration file."""
    import jax
    import jax.numpy as jnp

    from bench import weights as W
    from repro.models import api

    abstract = api.abstract_model(pcfg)[0]
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    L = cfg["num_hidden_layers"]
    E = cfg.get("num_local_experts", 0)
    words = W.seed_words(seed)
    plan = []
    for path, leaf in flat:
        name = _leaf_name(path)
        shape = tuple(leaf.shape)
        stacked = str(getattr(path[0], "key", "")) == "groups"
        experts = 0
        if stacked:
            if shape[0] != L:
                raise ValueError(f"{name}: {shape[0]} layers, want {L}")
            per = shape[1:]
            if name.startswith("moe/") and len(per) == 3:
                experts, per = per[0], per[1:]
                if experts < E:
                    raise ValueError(f"{name}: {experts} experts < {E}")
        else:
            per = shape
        plan.append((name, per, stacked, experts, leaf.dtype))

    def build():
        out = []
        for name, per, stacked, experts, dtype in plan:
            if name == "moe/router" and per[-1] > E:
                # the program pads the router's expert columns; the pad
                # experts are never chosen, their columns stay zero
                w = W.draw_stacked(words, name, (per[0], E), L)
                w = jnp.pad(w, ((0, 0), (0, 0), (0, per[-1] - E)))
            elif stacked:
                w = W.draw_stacked(words, name, per, L, experts)
            else:
                w = W.draw(words, name, 0, 0, per)
            out.append(w.astype(dtype))
        return out

    return build, treedef


def make_params(pcfg, cfg: dict, seed: int):
    """The program's parameters, made on the device in one jitted call."""
    import jax
    build, treedef = param_builder(pcfg, cfg, seed)
    return jax.tree_util.tree_unflatten(treedef, jax.jit(build)())


def build_engine(cell: Cell, params, annotate: bool):
    """The engine as ``launch/serve.py`` builds it, on the cell's rung."""
    import jax

    from repro.core.sp_schema import default_sp_stacked
    from repro.obs import Telemetry
    from repro.serving import Engine, EngineConfig
    from repro.sparsity import SparsityPolicy

    r = cell.rung
    pcfg = program_config(cell.cfg)
    sp, policy = None, SparsityPolicy.dense()
    kw = {}
    if r["backend"] != "off":
        # jitted, so the column norms reduce each stacked weight without
        # a float32 copy of it (eagerly, a 6 x 8192 x 22016 weight needs
        # 4 GiB more than the chip has left beside the model)
        sp = jax.jit(lambda p: default_sp_stacked(
            p, pcfg, keep_frac=float(r["keep_frac"]),
            alpha=float(r.get("alpha", 1.0))))(params)
        policy = SparsityPolicy.uniform(r["backend"],
                                        k_max_frac=float(r["k_max_frac"]))
        kw["prefill_dense_frac"] = float(r["prefill_dense_frac"])
    sv = cell.serving
    ecfg = EngineConfig(max_slots=sv["max_slots"], max_len=sv["max_len"],
                        prefill_chunk=sv["prefill_chunk"], policy=policy,
                        prefill_strategy="chunked", **kw)
    tel = Telemetry(annotate_dispatch=True) if annotate else None
    eng = Engine(params, pcfg, ecfg, sp, telemetry=tel)
    eng.warmup()
    return eng
