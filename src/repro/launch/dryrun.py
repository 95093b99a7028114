import os


def force_dryrun_devices() -> None:
    """Spawn 512 placeholder CPU devices for production-mesh lowering.

    MUST run before jax's first backend initialization (jax locks the
    device count on first init).  Fired when this module is executed as
    the dry-run tool (``python -m repro.launch.dryrun``).  Deliberately
    NOT a plain-import side effect: importing the parsing helpers from a
    pytest process must not reconfigure that process's devices — tests
    must see the real single CPU device (see conftest.py), and the
    512-device layout perturbs XLA:CPU codegen enough to break bit-exact
    kernel-vs-oracle comparisons.
    """
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    # the placeholder devices are CPU devices by construction: pin the
    # platform so a dry-run started on an accelerator host never reaches
    # for the chip another process holds
    os.environ["JAX_PLATFORMS"] = "cpu"


if __name__ == "__main__":
    force_dryrun_devices()

"""Multi-pod dry-run + roofline cost extraction.

Required dry-run (deliverable e): for every (architecture x input-shape)
cell, ``jit(step).lower(...).compile()`` must succeed on BOTH the single-pod
(16, 16) = 256-chip mesh and the multi-pod (2, 16, 16) = 512-chip mesh,
recording ``memory_analysis()`` (fits-per-device proof) and
``cost_analysis()`` + the collective schedule for §Roofline.

Scan-aware cost extraction: XLA's cost_analysis counts a ``while`` body
ONCE regardless of trip count (verified empirically), so raw numbers from a
scan-over-layers program undercount by ~n_layers.  We therefore lower
*R-differential variants* (1 and 2 scanned layer-groups) and reconstruct

    total = V1 + (repeats - 1) * (V2 - V1)                  [exact]

which is exact whenever every *inner* scan has trip count 1 in the variant.
Attention archs achieve that by setting the attention/loss chunk sizes to
the full sequence (same flops/bytes as the chunked schedule — chunking
reassociates, it does not change totals).  SSM/hybrid mixers (mamba2,
mLSTM: chunkwise state recurrence; sLSTM: per-token recurrence) cannot —
their per-layer costs are measured from component variants at S = chunk
(where the trip count IS 1) and scaled linearly (their cost is provably
linear in S), with the sLSTM per-token body separated by a second
S-differential.  Decode steps have no inner scans: the R-differential is
exact for every architecture.

Usage:
  python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k --costs
  python -m repro.launch.dryrun --all --out results/
"""
import argparse
import dataclasses
import json
import re
import subprocess
import sys
import time
import traceback

SHAPES = {
    "train_4k": dict(seq=4096, batch=256, kind="train"),
    "prefill_32k": dict(seq=32768, batch=32, kind="prefill"),
    "decode_32k": dict(seq=32768, batch=128, kind="decode"),
    "long_500k": dict(seq=524288, batch=1, kind="decode"),
}

ARCH_IDS = [
    "internvl2-26b", "deepseek-v2-lite-16b", "qwen3-moe-30b-a3b",
    "whisper-small", "xlstm-1.3b", "granite-20b", "gemma2-9b",
    "minicpm3-4b", "gemma3-12b", "zamba2-1.2b",
]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e5m2": 1, "f8e4m3fn": 1,
    "f8e4m3": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2,
    "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_LINE = re.compile(
    r"=\s*(\(?[a-z0-9_,\[\]{}\s]*?\)?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_TOK = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def parse_collectives(hlo_text: str) -> dict:
    """Sum output bytes of every collective op, keyed by (op, group_size).
    Counts each op ONCE (scan bodies are handled by the R-differential)."""
    out: dict = {}
    for line in hlo_text.splitlines():
        m = _COLL_LINE.search(line)
        if not m or "-done" in line.split("=")[0]:
            continue
        lhs, op = m.group(1), m.group(2)
        nbytes = 0
        for dt, dims in _SHAPE_TOK.findall(lhs):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        gm = _GROUPS_IOTA.search(line)
        if gm:
            gsize = int(gm.group(2))
        else:
            gl = _GROUPS_LIST.search(line)
            gsize = len(gl.group(1).split(",")) if gl else 2
        key = f"{op}@{gsize}"
        rec = out.setdefault(key, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += nbytes
    return out


def _coll_diff(a: dict, b: dict) -> dict:
    """a - b per key, clipped at 0."""
    keys = set(a) | set(b)
    out = {}
    for k in keys:
        c = a.get(k, {"count": 0, "bytes": 0})
        d = b.get(k, {"count": 0, "bytes": 0})
        out[k] = {"count": max(c["count"] - d["count"], 0),
                  "bytes": max(c["bytes"] - d["bytes"], 0)}
    return out


def _coll_scale_add(*terms):
    """terms: list of (coeff, coll_dict); returns the weighted sum."""
    out: dict = {}
    for coeff, d in terms:
        for k, v in d.items():
            rec = out.setdefault(k, {"count": 0, "bytes": 0})
            rec["count"] += coeff * v["count"]
            rec["bytes"] += coeff * v["bytes"]
    return out


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------
def build_step(arch_cfg, shape_name, mesh, policy, *, loss_chunk=1024,
               compress=None):
    """Returns (lowered,) for the given cell on the given mesh."""
    import jax
    from ..core.policy import get_policy
    from ..models.transformer import Model
    from ..launch.mesh import dp_axes_of
    from ..optim.optimizer import OptConfig
    from ..train.train_step import jit_train_step
    from ..train.serve_step import make_decode_step, make_prefill

    sh = SHAPES[shape_name]
    pol = get_policy(policy)
    if arch_cfg.narrow_partials:
        pol = pol.replace(narrow_partials=True)
    from ..models.layers import set_seq_parallel
    set_seq_parallel(arch_cfg.seq_parallel)
    model = Model(cfg=arch_cfg, policy=pol)
    dp = dp_axes_of(mesh)
    if sh["kind"] == "train":
        jitted, args, _ = jit_train_step(
            model, OptConfig(), mesh, batch_size=sh["batch"],
            seq_len=sh["seq"], dp_axes=dp, remat=True,
            loss_chunk=loss_chunk, compress_grads=compress)
    elif sh["kind"] == "prefill":
        jitted, args = make_prefill(model, mesh, batch=sh["batch"],
                                    seq_len=sh["seq"], max_len=sh["seq"],
                                    dp_axes=dp)
    else:
        jitted, args = make_decode_step(model, mesh, batch=sh["batch"],
                                        max_len=sh["seq"], dp_axes=dp)
    return jitted, args


def lower_and_compile(arch_cfg, shape_name, mesh, policy, **kw):
    jitted, args = build_step(arch_cfg, shape_name, mesh, policy, **kw)
    t0 = time.time()
    lowered = jitted.lower(*args)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    return lowered, compiled, {"lower_s": round(t1 - t0, 2),
                               "compile_s": round(t2 - t1, 2)}


def compiled_record(compiled, times) -> dict:
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    txt = compiled.as_text()
    return {
        "times": times,
        "memory": {
            "peak_bytes": ma.peak_memory_in_bytes,
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
        },
        "hlo": {
            "flops": ca.get("flops", 0.0),
            "bytes_accessed": ca.get("bytes accessed", 0.0),
            "transcendentals": ca.get("transcendentals", 0.0),
        },
        "collectives_static": parse_collectives(txt),
    }


# ---------------------------------------------------------------------------
# required dry-run (one cell x one mesh)
# ---------------------------------------------------------------------------
def _apply_sets(cfg, sets):
    """Apply --set key=value overrides (typed by the dataclass field)."""
    if not sets:
        return cfg
    kw = {}
    for kv in sets:
        k, v = kv.split("=", 1)
        obj, attr = cfg, k
        if "." in k:                      # nested sub-config (mlstm.chunk=...)
            head, attr = k.split(".", 1)
            obj = getattr(cfg, head)
        cur = getattr(obj, attr)
        if isinstance(cur, bool):
            v = v.lower() in ("1", "true", "yes")
        elif isinstance(cur, int):
            v = int(v)
        elif isinstance(cur, float):
            v = float(v)
        if obj is cfg:
            kw[attr] = v
        else:
            kw[k.split(".")[0]] = dataclasses.replace(obj, **{attr: v})
    return dataclasses.replace(cfg, **kw)


def run_cell(arch: str, shape_name: str, multi_pod: bool, policy: str,
             compress=None, sets=None) -> dict:
    import jax
    from ..core import ops as tpops
    from ..models.registry import get_config
    from .mesh import make_production_mesh

    tpops.set_mixed_dot(True)   # HLO carries the MXU-native mixed dots
    cfg = _apply_sets(get_config(arch), sets)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "x".join(str(s) for s in mesh.devices.shape),
           "n_devices": mesh.devices.size, "policy": policy,
           "compress": compress, "sets": sets or []}
    if SHAPES[shape_name]["kind"] != "train" and compress:
        rec.update(ok=False, skipped="compress only applies to train")
        return rec
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        rec.update(ok=False,
                   skipped="full-attention arch: long_500k per assignment")
        return rec
    lowered, compiled, times = lower_and_compile(cfg, shape_name, mesh,
                                                 policy, compress=compress)
    rec.update(ok=True, **compiled_record(compiled, times))
    return rec


# ---------------------------------------------------------------------------
# roofline cost extraction (single-pod mesh only)
# ---------------------------------------------------------------------------
def _variant(cfg, groups: int, *, enc_layers=None, seq_chunks=None,
             drop_suffix=False, pattern=None, full_seq=None):
    kw = {}
    pat = pattern if pattern is not None else cfg.pattern
    prefix = cfg.prefix
    suffix = () if drop_suffix else cfg.suffix
    kw["pattern"] = pat
    kw["prefix"] = prefix
    kw["suffix"] = suffix
    kw["n_layers"] = len(prefix) + len(suffix) + len(pat) * groups
    kw["unroll_scan"] = True   # exact cost_analysis (no while-body undercount)
    if cfg.encoder is not None and enc_layers is not None:
        kw["encoder"] = dataclasses.replace(cfg.encoder,
                                            n_layers=enc_layers)
    if full_seq is not None:
        kw["attn_chunk"] = full_seq
    return dataclasses.replace(cfg, **kw)


def _measure(cfg, shape_name, mesh, policy, *, seq=None, batch=None,
             loss_chunk=None):
    """Lower one variant and return its per-device cost terms."""
    sh = dict(SHAPES[shape_name])
    if seq is not None:
        sh = dict(sh, seq=seq)
    if batch is not None:
        sh = dict(sh, batch=batch)
    name = "__tmp"
    local_shapes = {name: sh}
    SHAPES[name] = sh
    try:
        lowered, compiled, times = lower_and_compile(
            cfg, name, mesh, policy,
            loss_chunk=loss_chunk or sh["seq"])
        ca = compiled.cost_analysis()
        return {
            "flops": ca.get("flops", 0.0),
            "bytes": ca.get("bytes accessed", 0.0),
            "transcendentals": ca.get("transcendentals", 0.0),
            "coll": parse_collectives(compiled.as_text()),
            "times": times,
        }
    finally:
        del SHAPES[name]


def _lin(v1, v2, repeats):
    """v1 + (repeats-1)*(v2-v1) on scalar terms + collectives."""
    out = {}
    for k in ("flops", "bytes", "transcendentals"):
        out[k] = v1[k] + (repeats - 1) * max(v2[k] - v1[k], 0.0)
    out["coll"] = _coll_scale_add((1, v1["coll"]),
                                  (repeats - 1, _coll_diff(v2["coll"],
                                                           v1["coll"])))
    return out


def _scaled_diff(v1, v2, scale, count):
    """count * scale * (v2-v1)."""
    d = {k: max(v2[k] - v1[k], 0.0) * scale * count
         for k in ("flops", "bytes", "transcendentals")}
    d["coll"] = _coll_scale_add(
        (scale * count, _coll_diff(v2["coll"], v1["coll"])))
    return d


def _add(*terms):
    out = {k: sum(t[k] for t in terms)
           for k in ("flops", "bytes", "transcendentals")}
    out["coll"] = _coll_scale_add(*[(1, t["coll"]) for t in terms])
    return out


def cost_cell(arch: str, shape_name: str, policy: str, sets=None,
              compress=None) -> dict:
    """Scan-corrected per-device cost terms on the single-pod mesh."""
    import jax
    from ..configs.base import LayerSpec
    from ..core import ops as tpops
    from ..models.registry import get_config
    from .mesh import make_production_mesh

    tpops.set_mixed_dot(True)
    cfg = _apply_sets(get_config(arch), sets)
    mesh = make_production_mesh(multi_pod=False)
    sh = SHAPES[shape_name]
    seq = sh["seq"]
    kind = sh["kind"]
    rec = {"arch": arch, "shape": shape_name, "policy": policy,
           "mesh": "16x16", "n_devices": 256, "sets": sets or [],
           "compress": compress}
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        rec.update(ok=False, skipped="full-attention arch")
        return rec

    ssm_like = cfg.name.startswith(("xlstm", "zamba2"))
    windowed = (cfg.windowed_slice and kind != "decode" and not ssm_like
                and any(s.window for s in cfg.pattern))
    if windowed:
        # windowed-slice recipe: with KV slicing a local layer's cost is
        # LINEAR in S (each query chunk sees a fixed window+chunk slice),
        # so locals are measured by S-differential at small S (the inner
        # chunk map is counted once at both sizes and cancels into the
        # per-chunk body term, exactly like the sLSTM recipe) and globals
        # exactly at full S with chunk = S.
        s1 = max(4 * cfg.attn_chunk, 2048)
        local = tuple(s for s in cfg.pattern if s.window)[:1]
        glob = tuple(s for s in cfg.pattern if not s.window)[:1]
        n_local = sum(1 for s in cfg.layer_list() if s.window)
        n_glob = sum(1 for s in cfg.layer_list()
                     if not s.window and s.mixer in ("gqa", "mla"))
        v1 = _measure(_variant(cfg, 1, full_seq=seq), shape_name, mesh,
                      policy)
        v2 = _measure(_variant(cfg, 2, full_seq=seq), shape_name, mesh,
                      policy)
        base = {k: max(2 * v1[k] - v2[k], 0.0)
                for k in ("flops", "bytes", "transcendentals")}
        base["coll"] = _coll_diff(v1["coll"], _coll_diff(v2["coll"],
                                                         v1["coll"]))
        g1 = _measure(_variant(cfg, 1, pattern=glob, full_seq=seq),
                      shape_name, mesh, policy)
        g2 = _measure(_variant(cfg, 2, pattern=glob, full_seq=seq),
                      shape_name, mesh, policy)
        l1a = _measure(_variant(cfg, 1, pattern=local), shape_name, mesh,
                       policy, seq=s1)
        l2a = _measure(_variant(cfg, 2, pattern=local), shape_name, mesh,
                       policy, seq=s1)
        l1b = _measure(_variant(cfg, 1, pattern=local), shape_name, mesh,
                       policy, seq=2 * s1)
        l2b = _measure(_variant(cfg, 2, pattern=local), shape_name, mesh,
                       policy, seq=2 * s1)
        # d_a = proj(s1) + body (chunk map counted once); d_b = 2proj + body
        d_a = {k: max(l2a[k] - l1a[k], 0.0)
               for k in ("flops", "bytes", "transcendentals")}
        d_b = {k: max(l2b[k] - l1b[k], 0.0)
               for k in ("flops", "bytes", "transcendentals")}
        loc = {k: n_local * ((seq / s1) * max(d_b[k] - d_a[k], 0.0)
                             + (seq / cfg.attn_chunk)
                             * max(2 * d_a[k] - d_b[k], 0.0))
               for k in ("flops", "bytes", "transcendentals")}
        # collectives get the same proj/body decomposition: the layer
        # measured at 2*s1 carries 2x the token-proportional collectives
        c_a = _coll_diff(l2a["coll"], l1a["coll"])   # proj(s1)+body colls
        c_b = _coll_diff(l2b["coll"], l1b["coll"])   # 2 proj(s1)+body
        c_proj = _coll_diff(c_b, c_a)
        c_body = _coll_diff(c_a, c_proj)
        loc["coll"] = _coll_scale_add(
            (n_local * seq / s1, c_proj),
            (n_local * seq / cfg.attn_chunk, c_body))
        total = _add(base, _scaled_diff(g1, g2, 1.0, n_glob), loc)
        rec["method"] = (f"windowed (locals S-diff@{s1} x{n_local}, "
                         f"globals exact x{n_glob})")
    elif kind == "decode" or not ssm_like:
        # EXACT: R-differential; attention/loss chunks at full seq so every
        # inner scan in the variants has trip count 1.
        full_seq = seq if kind != "decode" else None
        enc1 = 1 if cfg.encoder is not None else None
        v1 = _measure(_variant(cfg, 1, enc_layers=enc1, full_seq=full_seq),
                      shape_name, mesh, policy)
        v2 = _measure(_variant(cfg, 2, enc_layers=enc1, full_seq=full_seq),
                      shape_name, mesh, policy)
        total = _lin(v1, v2, cfg.repeats)
        if cfg.encoder is not None:
            v3 = _measure(_variant(cfg, 1, enc_layers=2, full_seq=full_seq),
                          shape_name, mesh, policy)
            total = _add(total,
                         _scaled_diff(v1, v3, 1.0,
                                      cfg.encoder.n_layers - 1))
        rec["method"] = "R-diff exact" + (" +enc-diff" if cfg.encoder
                                          else "")
    elif cfg.name.startswith("zamba2"):
        # base from 2*V1 - V2 at full seq (miscounted inner bodies cancel),
        # + 32 mamba layers measured at S=chunk (trip 1) scaled by S/chunk,
        # + 6 shared-attention layers measured exactly at full seq.
        c = cfg.mamba.chunk
        v1 = _measure(_variant(cfg, 1, drop_suffix=True, full_seq=seq),
                      shape_name, mesh, policy)
        v2 = _measure(_variant(cfg, 2, drop_suffix=True, full_seq=seq),
                      shape_name, mesh, policy)
        base = {k: max(2 * v1[k] - v2[k], 0.0)
                for k in ("flops", "bytes", "transcendentals")}
        base["coll"] = _coll_diff(v1["coll"], _coll_diff(v2["coll"],
                                                         v1["coll"]))
        m_pat = (LayerSpec(mixer="mamba2", ffn="none"),)
        m1 = _measure(_variant(cfg, 1, pattern=m_pat, drop_suffix=True),
                      shape_name, mesh, policy, seq=c)
        m2 = _measure(_variant(cfg, 2, pattern=m_pat, drop_suffix=True),
                      shape_name, mesh, policy, seq=c)
        a_pat = (cfg.shared_block,)
        a1 = _measure(_variant(cfg, 1, pattern=a_pat, drop_suffix=True,
                               full_seq=seq), shape_name, mesh, policy)
        a2 = _measure(_variant(cfg, 2, pattern=a_pat, drop_suffix=True,
                               full_seq=seq), shape_name, mesh, policy)
        n_mamba = sum(1 for s in cfg.layer_list() if s.mixer == "mamba2")
        n_sh = sum(1 for s in cfg.layer_list() if s.mixer == "shared_attn")
        total = _add(base,
                     _scaled_diff(m1, m2, seq / c, n_mamba),
                     _scaled_diff(a1, a2, 1.0, n_sh))
        rec["method"] = f"ssm-decomposed (mamba@S={c} x{seq//c}, attn exact)"
    else:  # xlstm
        c = cfg.mlstm.chunk
        v1 = _measure(_variant(cfg, 1, full_seq=seq), shape_name, mesh,
                      policy)
        v2 = _measure(_variant(cfg, 2, full_seq=seq), shape_name, mesh,
                      policy)
        base = {k: max(2 * v1[k] - v2[k], 0.0)
                for k in ("flops", "bytes", "transcendentals")}
        base["coll"] = _coll_diff(v1["coll"], _coll_diff(v2["coll"],
                                                         v1["coll"]))
        m_pat = (LayerSpec(mixer="mlstm", ffn="none"),)
        m1 = _measure(_variant(cfg, 1, pattern=m_pat), shape_name, mesh,
                      policy, seq=c)
        m2 = _measure(_variant(cfg, 2, pattern=m_pat), shape_name, mesh,
                      policy, seq=c)
        # sLSTM: exact 1-layer cost at small S with the time scan fully
        # unrolled, scaled linearly (everything in the layer is linear in
        # S).  The earlier S-differential decomposition amplified fusion
        # noise by ~S and was abandoned (see EXPERIMENTS.md §Perf).
        from ..models import ssm as ssm_mod
        s_pat = (LayerSpec(mixer="slstm", ffn="none"),)
        s_small = 32
        ssm_mod.set_unroll_time(True)
        try:
            s1u = _measure(_variant(cfg, 1, pattern=s_pat), shape_name,
                           mesh, policy, seq=s_small)
            s2u = _measure(_variant(cfg, 2, pattern=s_pat), shape_name,
                           mesh, policy, seq=s_small)
        finally:
            ssm_mod.set_unroll_time(False)
        n_m = sum(1 for s in cfg.layer_list() if s.mixer == "mlstm")
        n_s = sum(1 for s in cfg.layer_list() if s.mixer == "slstm")
        slstm = _scaled_diff(s1u, s2u, seq / s_small, n_s)
        total = _add(base, _scaled_diff(m1, m2, seq / c, n_m), slstm)
        rec["method"] = (f"ssm-decomposed (mlstm@S={c} x{seq//c}, "
                         f"slstm unrolled@S=32 x{n_s})")
    rec.update(ok=True, **{k: total[k]
                           for k in ("flops", "bytes", "transcendentals")})
    rec["coll"] = total["coll"]
    counts = cfg.param_counts()
    rec["params"] = counts
    return rec


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
def all_cells():
    from ..models.registry import get_config
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES:
            if shape == "long_500k" and not cfg.sub_quadratic:
                continue
            yield arch, shape


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch")
    p.add_argument("--shape", choices=list(SHAPES))
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--policy", default="tp_bf16")
    p.add_argument("--compress", default=None)
    p.add_argument("--costs", action="store_true",
                   help="roofline cost extraction instead of plain compile")
    p.add_argument("--set", action="append", dest="sets", default=[],
                   help="config override key=value (repeatable)")
    p.add_argument("--json", default=None, help="write record to this file")
    p.add_argument("--all", action="store_true")
    p.add_argument("--out", default="results")
    p.add_argument("--skip-existing", action="store_true", default=True)
    args = p.parse_args(argv)

    if args.all:
        os.makedirs(args.out, exist_ok=True)
        jobs = []
        for arch, shape in all_cells():
            for mp in (False, True):
                tag = f"dryrun_{arch}_{shape}_{'pod2' if mp else 'pod1'}"
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--policy",
                       args.policy, "--json",
                       os.path.join(args.out, tag + ".json")]
                if mp:
                    cmd.append("--multi-pod")
                jobs.append((tag, cmd))
            tag = f"costs_{arch}_{shape}"
            jobs.append((tag, [sys.executable, "-m", "repro.launch.dryrun",
                               "--arch", arch, "--shape", shape, "--costs",
                               "--policy", args.policy, "--json",
                               os.path.join(args.out, tag + ".json")]))
        for tag, cmd in jobs:
            outfile = cmd[cmd.index("--json") + 1]
            if args.skip_existing and os.path.exists(outfile):
                print(f"[skip] {tag}")
                continue
            t0 = time.time()
            r = subprocess.run(cmd, capture_output=True, text=True,
                               env={**os.environ})
            ok = r.returncode == 0 and os.path.exists(outfile)
            print(f"[{'ok' if ok else 'FAIL'}] {tag} "
                  f"({time.time()-t0:.0f}s)")
            if not ok:
                err = {"tag": tag, "returncode": r.returncode,
                       "stderr": r.stderr[-4000:]}
                with open(outfile + ".err", "w") as f:
                    json.dump(err, f, indent=1)
        return

    assert args.arch and args.shape
    try:
        if args.costs:
            rec = cost_cell(args.arch, args.shape, args.policy,
                            sets=args.sets, compress=args.compress)
        else:
            rec = run_cell(args.arch, args.shape, args.multi_pod,
                           args.policy, compress=args.compress,
                           sets=args.sets)
    except Exception:
        rec = {"arch": args.arch, "shape": args.shape, "ok": False,
               "error": traceback.format_exc()[-4000:]}
        print(json.dumps(rec, indent=1))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(rec, f, indent=1)
        sys.exit(1)
    print(json.dumps(rec, indent=1, default=float))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rec, f, indent=1, default=float)


if __name__ == "__main__":
    main()
