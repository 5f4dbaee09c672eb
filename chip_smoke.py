#!/usr/bin/env python3
"""Smoke run of the reduction path on a TPU, through the entry points users call.

    python3 chip_smoke.py                 # one chip: every phase below
    python3 chip_smoke.py --chips 4       # four chips: the engine fan-out only

One chip, in one process:

  kernels     every Pallas kernel on the main path against its ``ref.py``
              oracle (the ``xla`` adapter), bit for bit;
  mgard       a NYX-shaped 512³ float32 field through ``api.compress`` →
              ``to_bytes`` → ``Compressed.from_bytes`` → ``api.decompress``
              at relative bound 1e-2: max error ≤ bound × range;
  zfp         the same field at rate 16: stream bytes and decoded array
              identical to the same call with ``backend="xla"``;
  huffman     int32 keys of that shape from a skewed 4096-symbol alphabet:
              lossless, bit-exact;
  engine      ``ExecutionEngine.compress_pytree``/``decompress_pytree`` on
              four 64 MiB ZFP leaves and four 1 MiB MGARD leaves, so a
              stacked ``shard_map`` bucket runs for each codec: streams
              byte-identical to ``api.compress_leaf``, decodes equal to
              ``api.decompress_leaf``, MGARD within its bound;
  checkpoint  one ``CheckpointManager.save``/``restore`` round trip with a
              32 MiB leaf, which takes the ``chunk_size="auto"`` stream and
              calibrates the cost model on a fresh machine.

``--chips 4`` runs the engine phase over a four-device ``data`` mesh on the
four ZFP leaves and compares it with the one-device serial per-leaf path,
nothing else.  The fan-out is the engine's and the same for every codec's
bucket; MGARD's stacked bucket is checked on one chip.

Data is generated on the device from ``--seed``.  Every phase prints its
wall time (compilation included), ratio, max error, the lowering each op
took, and device memory.  A failed check exits non-zero; the last line of
standard output is one JSON object naming the device, printed only when
every check held.  The script needs the ``repro`` package beside it (under
``src/``) and a TPU: on any other platform it exits non-zero and prints no
result.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

MGARD_BOUND = 1e-2
ZFP_RATE = 16
ALPHABET = 4096
EDGE = 512            # NYX snapshot edge (SDRBench NYX: 512³ float32)
LEAF_EDGE = 256       # engine ZFP leaves: 256³ float32 = 64 MiB
MGARD_LEAF_EDGE = 64   # engine MGARD leaves
CKPT_SHAPE = (2048, 4096)  # 32 MiB checkpoint leaf

PHASE_OPS = {
    "mgard": ("quantize_map", "dequantize_map", "histogram",
              "huffman_encode_lookup", "huffman_pack_stream",
              "huffman_decode_chunks"),
    "zfp": ("zfp_block_compress", "zfp_block_decompress"),
    "huffman": ("histogram", "huffman_encode_lookup", "huffman_pack_stream",
                "huffman_decode_chunks"),
}


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# data, generated on the device from the seed
# ---------------------------------------------------------------------------


def nyx_field(seed: int, edge: int):
    """NYX-like baryon density: log-normal of a red-spectrum multi-scale sum
    of plane waves, plus small-scale noise."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        kdir, kphase, knoise = jax.random.split(key, 3)
        n_modes = 18
        dirs = jax.random.normal(kdir, (n_modes, 3))
        dirs = dirs / jnp.linalg.norm(dirs, axis=1, keepdims=True)
        kmag = 2.0 ** (jnp.arange(n_modes) % 6)          # 1..32 cycles/box
        amp = 0.6 * kmag ** -1.2
        phase = jax.random.uniform(kphase, (n_modes,), maxval=2 * math.pi)
        g = jnp.arange(edge, dtype=jnp.float32) * (2 * math.pi / edge)
        x, y, z = g[:, None, None], g[None, :, None], g[None, None, :]
        s = 0.02 * jax.random.normal(knoise, (edge,) * 3, jnp.float32)
        for m in range(n_modes):
            k = dirs[m] * kmag[m]
            s = s + amp[m] * jnp.cos(k[0] * x + k[1] * y + k[2] * z + phase[m])
        return jnp.exp(s)

    return make(jax.random.PRNGKey(seed))


def skewed_keys(seed: int, edge: int):
    """int32 keys with P(k) ∝ 1/(k+1) over a 4096-symbol alphabet."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        u = jax.random.uniform(key, (edge,) * 3)
        k = jnp.floor(jnp.float32(ALPHABET) ** u).astype(jnp.int32) - 1
        return jnp.clip(k, 0, ALPHABET - 1).at[0, 0, 0].set(ALPHABET - 1)

    return make(jax.random.PRNGKey(seed + 1))


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------


def memory(devices) -> str:
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(
            f"dev{d.id}:in_use={st.get('bytes_in_use')},"
            f"peak={st.get('peak_bytes_in_use')},limit={st.get('bytes_limit')}"
        )
    return " ".join(parts)


def lowerings(ops, backend: str) -> str:
    from repro.core import adapters

    return ",".join(f"{op}={adapters.lowering(op, backend)}" for op in ops)


def report(phase: str, devices, **fields) -> None:
    items = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {items} mem[{memory(devices)}]", flush=True)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_kernels(devices, kernel: str, n: int) -> None:
    """Each main-path kernel under ``kernel`` equals the xla oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import huffman
    from repro.kernels.histogram import ops as hist
    from repro.kernels.huffman_decode import ops as dec
    from repro.kernels.huffman_encode import ops as enc
    from repro.kernels.quantize_map import ops as quant
    from repro.kernels.zfp_block import ops as zfp

    t0 = time.perf_counter()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=n).astype(np.float32))
    lv = jnp.asarray(rng.integers(0, 10, n).astype(np.int32))
    bins = jnp.asarray((10.0 ** -rng.uniform(2, 4, 10)).astype(np.float32))
    zipf = np.minimum(rng.zipf(1.3, n) - 1, ALPHABET - 1).astype(np.int32)
    keys = jnp.asarray(zipf)
    cases, walls = {}, {}

    def both(name, fn, *args):
        t = time.perf_counter()
        a, b = fn(*args, adapter=kernel), fn(*args, adapter="xla")
        flat_a, flat_b = jax.tree.leaves(a), jax.tree.leaves(b)
        cases[name] = all(
            u.shape == v.shape and u.dtype == v.dtype and bool(jnp.array_equal(u, v))
            for u, v in zip(flat_a, flat_b)
        )
        walls[name] = round(time.perf_counter() - t, 3)

    both("quantize_map", quant.quantize, x, lv, bins)
    u = quant.quantize(x, lv, bins, adapter="xla")
    both("dequantize_map", quant.dequantize, u, lv, bins)
    for nb in (ALPHABET, 1 << 16):
        both(f"histogram[{nb}]", hist.histogram, keys, nb)
    freq = np.bincount(zipf, minlength=ALPHABET)
    book = huffman.build_codebook(freq)
    codes_t, lens_t = jnp.asarray(book.codes), jnp.asarray(book.lengths)
    both("huffman_encode_lookup", enc.encode_lookup, keys, codes_t, lens_t)
    codes, lens = enc.encode_lookup(keys, codes_t, lens_t, adapter="xla")
    total = int(np.sum(freq * book.lengths))
    words, offs, _ = enc.pack_stream(codes, lens, total // 32 + 1,
                                     huffman.DEFAULT_CHUNK, adapter="xla")
    t = huffman.decode_tables(np.asarray(book.lengths, np.int32))
    both("huffman_decode_chunks", dec.decode_chunks, words, offs,
         jnp.asarray(t.first_code), jnp.asarray(t.count),
         jnp.asarray(t.sym_offset), jnp.asarray(t.sym_sorted),
         huffman.DEFAULT_CHUNK, t.max_len)
    decoded = dec.decode_chunks(
        words, offs, jnp.asarray(t.first_code), jnp.asarray(t.count),
        jnp.asarray(t.sym_offset), jnp.asarray(t.sym_sorted),
        huffman.DEFAULT_CHUNK, t.max_len, adapter=kernel,
    )
    cases["huffman_decode_roundtrip"] = bool(
        jnp.array_equal(decoded.reshape(-1)[:n], keys)
    )
    nblk = max(8, n // 64)
    blocks = x[: nblk * 64].reshape(nblk, 64) if n >= 512 else jnp.asarray(
        rng.normal(size=(nblk, 64)).astype(np.float32))
    for dims, rate in ((3, ZFP_RATE), (3, 28), (2, 12), (1, 8)):
        bs = 4 ** dims
        b = blocks.reshape(-1, bs).T  # coefficient-major (4^d, N)
        both(f"zfp_block_compress[d{dims}r{rate}]", zfp.compress_blocks, b, rate, dims)
        p, e = zfp.compress_blocks(b, rate, dims, adapter="xla")
        both(f"zfp_block_decompress[d{dims}r{rate}]", zfp.decompress_blocks,
             p, e, rate, dims)
    report("kernels", devices, wall_s=time.perf_counter() - t0, n=n,
           adapter=kernel,
           results=",".join(f"{k}={'ok' if v else 'MISMATCH'}:{walls.get(k)}s"
                            for k, v in cases.items()))
    bad = [k for k, v in cases.items() if not v]
    check(not bad, f"kernel differs from its oracle: {bad}")


def _roundtrip(x, method: str, **kw):
    import jax

    from repro.core import api
    from repro.core.container import Compressed

    t0 = time.perf_counter()
    c = api.compress(x, method, **kw)
    raw = c.to_bytes()
    out = api.decompress(Compressed.from_bytes(raw))
    jax.block_until_ready(out)
    return c, raw, out, time.perf_counter() - t0


def phase_fields(devices, backend: str, seed: int, edge: int) -> None:
    import jax.numpy as jnp

    from repro.core import adapters

    kernel = adapters.resolve_backend(backend)
    field = nyx_field(seed, edge)
    vmin, vmax = float(jnp.min(field)), float(jnp.max(field))
    rng_ = vmax - vmin

    from repro.core.context import GLOBAL_CMM

    c, raw, out, wall = _roundtrip(field, "mgard", error_bound=MGARD_BOUND,
                                   backend=backend)
    err = float(jnp.max(jnp.abs(out - field)))
    report("mgard", devices, wall_s=wall, shape=tuple(field.shape),
           ratio=c.ratio(), stream_bytes=len(raw), max_err=err,
           bound=MGARD_BOUND * rng_, lowering=lowerings(PHASE_OPS["mgard"], kernel))
    check(out.shape == field.shape, "mgard decode shape")
    check(err <= MGARD_BOUND * rng_, f"mgard max error {err} > {MGARD_BOUND} × range {rng_}")
    del c, raw, out
    GLOBAL_CMM.clear()

    c, raw, out, wall = _roundtrip(field, "zfp", rate=ZFP_RATE, backend=backend)
    cx, raw_x, out_x, wall_x = _roundtrip(field, "zfp", rate=ZFP_RATE, backend="xla")
    err = float(jnp.max(jnp.abs(out - field)))
    report("zfp", devices, wall_s=wall, wall_xla_s=wall_x, ratio=c.ratio(),
           stream_bytes=len(raw), max_err=err, rel_err=err / rng_,
           lowering=lowerings(PHASE_OPS["zfp"], kernel),
           bytes_equal_xla=raw == raw_x,
           decode_equal_xla=bool(jnp.array_equal(out, out_x)))
    check(raw == raw_x, "zfp stream bytes differ from backend='xla'")
    check(bool(jnp.array_equal(out, out_x)), "zfp decode differs from backend='xla'")
    check(out.shape == field.shape and bool(jnp.all(jnp.isfinite(out))),
          "zfp decode is not a finite field of the input's shape")
    del c, raw, out, cx, raw_x, out_x, field
    GLOBAL_CMM.clear()

    keys = skewed_keys(seed, edge)
    c, raw, out, wall = _roundtrip(keys, "huffman", backend=backend)
    exact = bool(jnp.array_equal(out, keys))
    report("huffman", devices, wall_s=wall, ratio=c.ratio(),
           stream_bytes=len(raw), kmax=int(jnp.max(keys)), exact=exact,
           lowering=lowerings(PHASE_OPS["huffman"], kernel))
    check(out.dtype == keys.dtype and exact, "huffman round trip is not bit-exact")


def _leaf_tree(seed: int, edge: int, mgard_edge: int | None) -> dict:
    import numpy as np

    tree = {f"zfp{i}": np.asarray(nyx_field(seed + 20 + i, edge)) for i in range(4)}
    if mgard_edge:
        tree.update({f"mgard{i}": np.asarray(nyx_field(seed + 24 + i, mgard_edge))
                     for i in range(4)})
    return tree


def phase_engine(devices, backend: str, seed: int, edge: int,
                 mgard_edge: int | None) -> None:
    """Stacked engine buckets vs the serial per-leaf path."""
    import numpy as np

    from repro.core import adapters, api
    from repro.core.engine import ExecutionEngine
    from repro.launch.mesh import make_data_mesh

    kernel = adapters.resolve_backend(backend)
    tree = _leaf_tree(seed, edge, mgard_edge)

    def select(key, arr):
        if key.startswith("mgard"):
            return "mgard", {"error_bound": MGARD_BOUND, "backend": backend}
        return "zfp", {"rate": ZFP_RATE, "backend": backend}

    mesh = make_data_mesh(len(devices))
    with ExecutionEngine(mesh=mesh, backend=backend) as eng:
        t0 = time.perf_counter()
        comp, stats = eng.compress_pytree(tree, select=select)
        t_enc = time.perf_counter() - t0
        print(f"[engine] encoded in {t_enc} s", flush=True)
        t0 = time.perf_counter()
        out = eng.decompress_pytree(comp, tree)
        t_dec = time.perf_counter() - t0
        est = eng.stats()
    mesh_ids = tuple(sorted(d.id for d in mesh.devices.flat))
    report("engine", devices, encode_wall_s=t_enc, decode_wall_s=t_dec,
           leaves=len(tree), zfp_leaf_bytes=tree["zfp0"].nbytes,
           mgard_leaf_bytes=tree["mgard0"].nbytes if mgard_edge else 0,
           ratio=stats["ratio"], sharded_leaves=stats["sharded_leaves"],
           sharded_decoded=est["sharded_decoded_leaves"],
           shard_map_calls=est["shard_map_calls"],
           stack_devices=est["stack_devices"], mesh_devices=mesh_ids,
           lowering=lowerings(PHASE_OPS["zfp"] + (PHASE_OPS["mgard"] if mgard_edge
                                                  else ()), kernel))
    check(stats["sharded_leaves"] == len(tree), "not every leaf rode a stacked bucket")
    check(est["sharded_decoded_leaves"] == len(tree), "not every leaf decoded stacked")
    check(est["stack_devices"] == mesh_ids,
          f"stacked work on {est['stack_devices']}, mesh is {mesh_ids}")

    t0 = time.perf_counter()
    mgard_err = 0.0
    for key, arr in tree.items():
        method, params = select(key, arr)
        serial = api.compress_leaf(arr, method, **params)
        check(serial.to_bytes() == comp[key].to_bytes(),
              f"engine stream of {key} differs from api.compress_leaf")
        ref = api.decompress_leaf(comp[key])
        check(np.array_equal(np.asarray(out[key]), ref),
              f"engine decode of {key} differs from api.decompress_leaf")
        if method == "mgard":
            err = float(np.max(np.abs(ref - arr)))
            bound = MGARD_BOUND * float(np.ptp(arr))
            check(err <= bound, f"mgard leaf {key}: max error {err} > {bound}")
            mgard_err = max(mgard_err, err / bound)
    report("engine-vs-serial", devices, wall_s=time.perf_counter() - t0,
           serial_device=devices[0].id, bytes_identical=True,
           decode_identical=True, mgard_max_err_over_bound=mgard_err)


def phase_checkpoint(devices, backend: str, seed: int, shape) -> None:
    import numpy as np

    from repro.checkpoint import CheckpointManager, CheckpointPolicy
    from repro.runtime import calibrate

    n = math.prod(shape)
    edge = round(n ** (1 / 3))
    w = np.asarray(nyx_field(seed + 30, edge)).reshape(-1)[:n]
    w = np.pad(w, (0, n - w.size), mode="wrap").reshape(shape)
    tree = {"w": w, "b": np.linspace(-1, 1, 1000, dtype=np.float32)}
    sweeps = calibrate.SWEEPS_RUN
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, CheckpointPolicy())
        t0 = time.perf_counter()
        rep = mgr.save(0, tree)
        t_save = time.perf_counter() - t0
        print(f"[checkpoint] saved in {t_save} s", flush=True)
        t0 = time.perf_counter()
        restored, _ = mgr.restore(0, target=tree)
        t_restore = time.perf_counter() - t0
    err = float(np.max(np.abs(np.asarray(restored["w"]) - w)))
    scale = float(np.max(np.abs(w)))
    report("checkpoint", devices, save_s=t_save, restore_s=t_restore,
           leaf_bytes=w.nbytes, ratio=rep.get("ratio"), max_err=err,
           rel_err=err / scale, calibration_sweeps=calibrate.SWEEPS_RUN - sweeps)
    check(np.asarray(restored["w"]).shape == w.shape, "restored leaf shape")
    check(err <= 1e-5 * scale, f"checkpoint leaf error {err} vs scale {scale}")
    check(np.array_equal(np.asarray(restored["b"]), tree["b"]),
          "small leaf is not restored bit-exact")


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run(chips: int, seed: int, *, backend: str = "auto", edge: int = EDGE,
        leaf_edge: int = LEAF_EDGE, mgard_leaf_edge: int = MGARD_LEAF_EDGE,
        ckpt_shape=CKPT_SHAPE, kernel_n: int = (1 << 22) + 77) -> list:
    """Every phase for ``chips`` devices (the engine phase only for > 1).

    Returns the devices the phases ran on."""
    import jax

    from repro.core import adapters

    from repro.core.context import GLOBAL_CMM

    devices = jax.devices()[:chips]
    if chips > 1:
        phase_engine(devices, backend, seed, leaf_edge, None)
        return devices
    phases = (
        lambda: phase_kernels(devices, adapters.resolve_backend(backend), kernel_n),
        lambda: phase_fields(devices, backend, seed, edge),
        lambda: phase_engine(devices, backend, seed, leaf_edge, mgard_leaf_edge),
        lambda: phase_checkpoint(devices, backend, seed, ckpt_shape),
    )
    for phase in phases:
        phase()
        GLOBAL_CMM.clear()  # phases share no plan: free their device workspace
    return devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: the repro package is not beside this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    cache = {"hits": 0, "misses": 0}

    def on_event(name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devs[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked for, {len(devs)} present",
              file=sys.stderr)
        return 1
    print(f"[setup] devices={[(d.id, d.device_kind) for d in devs]} "
          f"jax={jax.__version__} compile_cache={cache_dir}", flush=True)
    t0 = time.perf_counter()
    try:
        used = run(args.chips, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"[done] wall_s={time.perf_counter() - t0} "
          f"compile_cache_hits={cache['hits']} misses={cache['misses']}",
          flush=True)
    d0 = used[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": len(used),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
