"""Sharded model-zoo FL round throughput (engine/zoo.py, DESIGN.md §14).

How fast does one full OBCSAA round (surrogate grads → 1-bit compress →
packed int32 MAC → AWGN → chunked decode → update) run when the parameter
vector is partitioned over the whole 8-device mesh and NOTHING dense at
full D is ever replicated? Measured as rounds/sec on the host mesh
(4 FL workers × 2 model shards), per architecture.

Methodology:

- On the CPU every measurement runs in a CHILD process so the 8-device
  XLA host flag never leaks into the caller (the bench harness keeps 1
  device). On a TPU the rows run in the calling process, which holds the
  chips; each row's workers x model mesh must match the local chips, and
  a row whose mesh they cannot make up fails.
- Default rows are CI-scale: a parity gate (the 16k-element geometry of
  tests/test_zoo.py — the sharded round must stay within the parity
  tolerances of ``repro.engine.parity`` of the single-device reference
  over a 2-round chain) plus smoke-config rounds for two architectures.
  CI asserts the parity flag, never a timing ratio (the PR-3 convention).
- ``--full`` regenerates the zoo-scale row: the gemma2-2b FULL config
  (2.614B parameters — the ≥1B acceptance config) with the wide-chunk
  geometry D_c=16384, S_c=32, κ_c=8. Parameters stay 8-way sharded
  (1.3 GB/device); each worker column gathers one model-half and
  compresses it in 64-chunk ``lax.map`` blocks, so peak memory is bounded
  by the half + decode workspace, not U×D. The measured row is cached in
  experiments/bench_cache.json and replayed by default runs.

Real-backward rows (``zoo-train/*``, engine/zoo_train.py, DESIGN.md §16)
sit NEXT TO the surrogate rows: the same round driven by genuine eq. 3
gradients of the scanned stacked-layer model, computed parameter-sharded
with cotangents landing directly in the owned chunk rows (no host
round-trip, no full-D gather). CI-scale train rows run fresh each time —
``parity-gemma2-smoke`` gates a 3-round REAL-gradient chain against the
single-device oracle within the same tolerances — and every row reports
peak process RSS (`peak_rss_mb`; on the host-device mesh peak RSS IS the
device memory bound), plus the device's own peak where it reports one
(`peak_device_mb`). The ≥1B real-backward row is cached under ``zoo:v2:full``
and regenerated by ``--full``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import CACHE_PATH, cached_rows, emit

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
SRC = os.path.join(ROOT, "src")

FULL_KEY = "zoo:v1:full"          # surrogate-grads ≥1B row
TRAIN_KEY = "zoo:v3"              # real-backward CI rows (state-carry API)
TRAIN_FULL_KEY = "zoo:v2:full"    # real-backward ≥1B row

def _peak_fields() -> dict:
    """Peak process RSS (on the host-device mesh it IS the device memory
    bound) and, where the device reports it, its peak bytes in use."""
    import resource

    import jax
    out = {"peak_mb": resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss / 1024.0}
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        out["peak_device_mb"] = stats["peak_bytes_in_use"] / 2**20
    return out


def run_round(spec: dict) -> dict:
    """One surrogate-gradient zoo row (engine/zoo.py) on the spec's
    workers x model mesh."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from repro.core.obcsaa import OBCSAAConfig
    from repro.engine import parity
    from repro.engine.zoo import build_zoo_round
    from repro.launch.mesh import make_zoo_mesh

    mesh = make_zoo_mesh(spec["workers"], spec["mp"])
    if spec.get("arch"):
        from repro.configs import get_config, get_smoke_config
        from repro.models.registry import build_model
        cfg = (get_smoke_config if spec["smoke"] else get_config)(
            spec["arch"])
        shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
        D = sum(int(np.prod(l.shape))
                for l in jax.tree_util.tree_leaves(shapes))
    else:
        D = spec["D"]
    ob = OBCSAAConfig(**spec["ob"])
    zr = build_zoo_round(ob, D, mesh)
    params = jax.jit(
        lambda: jnp.zeros((zr.n_chunks, ob.chunk), jnp.float32),
        out_shardings=NamedSharding(mesh, zr.spec))()

    key = jax.random.PRNGKey(0)
    t0 = time.time()
    params, st = zr.round_gen(params, 0, key, 1e-4, 10.0, 0.05)
    jax.block_until_ready(params)
    compile_s = time.time() - t0
    t0 = time.time()
    for t in range(1, 1 + spec["rounds"]):
        params, st = zr.round_gen(params, t, key, 1e-4, 10.0, 0.05)
    jax.block_until_ready(params)
    wall = time.time() - t0

    out = {"D": D, "D_pad": zr.D_pad, "n_chunks": zr.n_chunks,
           "workers": zr.U, "mp": zr.n_model, "rounds": spec["rounds"],
           "compile_s": compile_s, "wall_s": wall, **_peak_fields(),
           "ghat_norm": float(st.ghat_norm),
           "finite": bool(np.isfinite(float(st.ghat_norm)))}
    if spec.get("parity"):
        rc = zr.chunk_params(jnp.zeros((D,), jnp.float32))
        for t in range(1 + spec["rounds"]):
            rc, _ = zr.reference_round(rc, t, key, 1e-4, 10.0, 0.05)
        out["parity"] = parity.compare_leaf(
            "master", params, rc, parity.TOLERANCES["master"]).ok
    return out


def run_train(spec: dict) -> dict:
    """One real-backward zoo-train row (engine/zoo_train.py, DESIGN.md
    §16): genuine eq. 3 gradients of the scanned stacked-layer model,
    produced already in the compressor's chunk layout — no host
    round-trip, no full-D gather."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config, get_smoke_config
    from repro.core.obcsaa import OBCSAAConfig
    from repro.engine import parity
    from repro.engine.zoo_train import build_zoo_train_round
    from repro.launch.mesh import make_zoo_mesh
    from repro.models.registry import build_model

    mesh = make_zoo_mesh(spec["workers"], spec["mp"])
    cfg = (get_smoke_config if spec["smoke"] else get_config)(spec["arch"])
    model = build_model(cfg)
    ob = OBCSAAConfig(**spec["ob"])
    zr = build_zoo_train_round(model, mesh, ob,
                               remat=spec.get("remat", "full"),
                               optimizer=spec.get("optimizer", "sgd"),
                               opt_kwargs=spec.get("opt_kwargs"),
                               error_feedback=spec.get("error_feedback",
                                                       False))
    chunked0 = zr.chunk_params(model.init(jax.random.PRNGKey(0)))
    state = zr.shard_state(zr.init_state(chunked0))
    if not spec.get("parity"):
        del chunked0          # only the oracle chain needs the host copy
    rng = np.random.default_rng(0)
    tok = rng.integers(0, cfg.vocab_size,
                       (zr.U, spec["batch"], spec["seq"])).astype(np.int32)
    raw = {"tokens": jnp.asarray(tok),
           "targets": jnp.asarray(np.roll(tok, -1, -1))}
    batch = zr.shard_batch(raw)
    key = jax.random.PRNGKey(0)
    t0 = time.time()
    state, st = zr.round_train(state, batch, 0, key, 1e-4, 10.0, 0.05)
    jax.block_until_ready(state.master)
    compile_s = time.time() - t0
    t0 = time.time()
    for t in range(1, 1 + spec["rounds"]):
        state, st = zr.round_train(state, batch, t, key, 1e-4, 10.0,
                                   0.05)
    jax.block_until_ready(state.master)
    wall = time.time() - t0
    finite = bool(np.isfinite(float(st.loss))
                  and np.isfinite(float(st.ghat_norm))
                  and all(np.isfinite(np.asarray(x)).all()
                          for x in st.budget))
    out = {"D": zr.D, "D_pad": zr.D_pad, "n_chunks": zr.n_chunks,
           "workers": zr.U, "mp": zr.n_model,
           "rounds": spec["rounds"], "compile_s": compile_s,
           "wall_s": wall, **_peak_fields(),
           "loss": float(st.loss), "finite": finite}
    if spec.get("parity"):
        # parity covers EVERY carry leaf: master + optimizer moments +
        # EF residuals (DESIGN.md §17)
        rc = zr.init_state(chunked0)
        for t in range(1 + spec["rounds"]):
            rc, _ = zr.reference_round_train(rc, raw, t, key, 1e-4, 10.0,
                                             0.05)
        out["parity"] = all(r.ok for r in parity.compare_states(
            state, rc, master0=chunked0))
    return out


# A child runs one row on the 8-device host mesh; the flag never leaks
# into the caller (the bench harness keeps 1 device).
CHILD = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    from benchmarks import zoo_bench
    res = getattr(zoo_bench, sys.argv[1])(json.loads(sys.argv[2]))
    print("ZOO_RESULT " + json.dumps(res))
""")


def _child(spec: dict, timeout: int, fn=run_round) -> dict:
    """Run one row: in this process on a TPU (a child could not reach the
    chip this process holds), else in a child on the host mesh."""
    import jax
    if jax.default_backend() == "tpu":
        return fn(spec)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [SRC, ROOT] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    r = subprocess.run([sys.executable, "-c", CHILD, fn.__name__,
                        json.dumps(spec)],
                       env=env, capture_output=True, text=True,
                       timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError(f"zoo child failed:\n{r.stderr[-3000:]}")
    line = [l for l in r.stdout.splitlines()
            if l.startswith("ZOO_RESULT ")][-1]
    return json.loads(line[len("ZOO_RESULT "):])


def _row(name: str, res: dict) -> tuple:
    us = 1e6 * res["wall_s"] / max(res["rounds"], 1)
    rate = res["rounds"] / res["wall_s"] if res["wall_s"] > 0 else 0.0
    derived = (f"D={res['D']};mesh={res['workers']}x{res['mp']};"
               f"rounds_per_s={rate:.4g};compile_s={res['compile_s']:.1f};"
               f"finite={res['finite']}")
    if "peak_mb" in res:
        derived += f";peak_rss_mb={res['peak_mb']:.0f}"
    if "peak_device_mb" in res:
        derived += f";peak_device_mb={res['peak_device_mb']:.0f}"
    if "loss" in res:
        derived += f";loss={res['loss']:.4f}"
    if "parity" in res:
        derived += f";parity={res['parity']}"
    return (name, us, derived)


SMOKE_OB = dict(chunk=1024, measure=128, topk=32, biht_iters=3,
                recon_alg="iht", spmd_topk=True, packed=True,
                bisect_iters=16)
PARITY_OB = dict(chunk=256, measure=64, topk=16, biht_iters=3,
                 recon_alg="iht", spmd_topk=True, packed=True,
                 bisect_iters=16)
# ≥1B geometry: wide chunks keep n_chunks (and the decode batch) bounded;
# S_c=32 is one packed uint32 word per chunk on the wire
FULL_OB = dict(chunk=16384, measure=32, topk=8, biht_iters=2,
               recon_alg="iht", spmd_topk=True, packed=True,
               bisect_iters=10)


def _smoke_rows():
    rows = [_row("zoo/parity-16k", _child(
        {"D": 16000, "ob": PARITY_OB, "rounds": 2, "workers": 4, "mp": 2,
         "parity": True}, timeout=600))]
    for arch in ("gemma2-2b", "mamba2-2.7b"):
        rows.append(_row(f"zoo/{arch}-smoke", _child(
            {"arch": arch, "smoke": True, "ob": SMOKE_OB, "rounds": 3,
             "workers": 4, "mp": 2}, timeout=600)))
    return rows


def _train_smoke_rows():
    """Real-backward CI rows: a parity gate (sharded round chain with
    genuine scanned-layer gradients vs the single-device oracle), the
    stateful-optimizer gate (adam moments + per-worker EF residuals as
    sharded carries, every carry leaf within tolerance — DESIGN.md §17),
    plus a second-arch timing row with peak RSS."""
    rows = [_row("zoo-train/parity-gemma2-smoke", _child(
        {"arch": "gemma2-2b", "smoke": True, "ob": PARITY_OB, "rounds": 2,
         "workers": 4, "mp": 2, "batch": 2, "seq": 32, "parity": True},
        timeout=1800, fn=run_train))]
    rows.append(_row("zoo-train/parity-opt-ef-smoke", _child(
        {"arch": "gemma2-2b", "smoke": True, "ob": PARITY_OB, "rounds": 2,
         "workers": 4, "mp": 2, "batch": 2, "seq": 32, "parity": True,
         "optimizer": "adam", "error_feedback": True},
        timeout=1800, fn=run_train)))
    rows.append(_row("zoo-train/mamba2-2.7b-smoke", _child(
        {"arch": "mamba2-2.7b", "smoke": True, "ob": SMOKE_OB, "rounds": 2,
         "workers": 4, "mp": 2, "batch": 2, "seq": 32},
        timeout=1800, fn=run_train)))
    return rows


def _full_rows():
    res = _child({"arch": "gemma2-2b", "smoke": False, "ob": FULL_OB,
                  "rounds": 1, "workers": 4, "mp": 2}, timeout=14400)
    assert res["D"] >= 1_000_000_000, res
    return [_row("zoo/gemma2-2b-2.6B", res)]


def _train_full_rows():
    """The ≥1B acceptance run with REAL gradients: gemma2-2b full config
    (2.614B), each worker's backward runs parameter-sharded and its
    cotangents land directly in the owned chunk rows.  2 workers x 4
    model shards: the deeper model split keeps per-device gathered
    sections (and their cotangents) at a quarter of D, which is what
    fits host RAM when every simulated device pays them at once."""
    res = _child({"arch": "gemma2-2b", "smoke": False, "ob": FULL_OB,
                  "rounds": 1, "workers": 2, "mp": 4, "batch": 1,
                  "seq": 32}, timeout=14400, fn=run_train)
    assert res["D"] >= 1_000_000_000, res
    return [_row("zoo-train/gemma2-2b-2.6B", res)]


def _store(key: str, rows):
    cache = json.loads(CACHE_PATH.read_text()) if CACHE_PATH.exists() else {}
    cache[key] = [list(r) for r in rows]
    CACHE_PATH.parent.mkdir(parents=True, exist_ok=True)
    CACHE_PATH.write_text(json.dumps(cache, indent=1))


def main(full: bool = False):
    """CI-scale rows run FRESH every time (they carry the parity gates —
    surrogate AND real-backward); the ≥1B rows replay from
    experiments/bench_cache.json unless --full regenerates them."""
    rows = _smoke_rows()
    _store("zoo:v1", rows)        # make_experiments_md reads the cache
    emit(rows)
    trows = _train_smoke_rows()
    _store(TRAIN_KEY, trows)
    emit(trows)
    if full:
        frows = _full_rows()
        _store(FULL_KEY, frows)
        emit(frows)
        tfrows = _train_full_rows()
        _store(TRAIN_FULL_KEY, tfrows)
        emit(tfrows)
    else:
        frows = cached_rows(FULL_KEY) or []
        emit(frows)
        tfrows = cached_rows(TRAIN_FULL_KEY) or []
        emit(tfrows)
    return rows + trows + frows + tfrows


if __name__ == "__main__":
    main(full="--full" in sys.argv)
