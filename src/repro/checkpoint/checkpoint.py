"""Sharded checkpointing with manifest + reshard-on-restore.

Layout (one directory per step):
    <dir>/step_000100/
        manifest.json            — tree structure, shapes, dtypes, step,
                                   mesh shape, data-stream cursor
        shard_<k>.npz            — flat arrays owned by host k (single-host
                                   runs write shard_0 with everything)
        _COMMITTED               — atomic commit marker (written last)

Fault-tolerance contract (runtime/fault_tolerance.py):
  * restore() ignores uncommitted (crashed mid-write) checkpoints;
  * arrays are restorable onto a DIFFERENT mesh: values are saved unsharded
    (gathered) per leaf, and re-sharded by the caller's shardings on load —
    elastic restarts change the mesh without touching the checkpoint;
  * save is atomic-per-step and keeps the newest ``keep`` steps;
  * integrity: every array gets a sha256 in the manifest at save time and
    is verified on load (``verify=False`` opts out) — a truncated or
    bit-flipped shard raises ``CheckpointCorrupt`` naming the bad leaf
    instead of silently serving garbage weights;
  * stale ``*.tmp`` directories from crashed saves are detected and
    cleaned when a ``CheckpointManager`` opens the directory.
"""

from __future__ import annotations

import glob
import hashlib
import json
import os
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np


class CheckpointCorrupt(RuntimeError):
    """A committed checkpoint failed integrity verification (truncated
    archive, bit-flipped array, missing leaf).  The message names the
    offending file/leaf; the operator restores from an older step or
    re-exports the payload."""


def _sha256(arr: np.ndarray) -> str:
    """Content hash of one array as stored: dtype + shape + raw bytes, so
    a reinterpreted (right bytes, wrong dtype) leaf also fails."""
    a = np.ascontiguousarray(arr)
    h = hashlib.sha256()
    h.update(str(a.dtype).encode())
    h.update(str(a.shape).encode())
    h.update(a.tobytes())
    return h.hexdigest()


def _load_npz(path: str):
    """np.load with corruption mapped to CheckpointCorrupt (a truncated or
    bit-flipped zip raises BadZipFile/zlib.error/ValueError deep inside
    numpy — surface them as one typed error)."""
    try:
        return np.load(path)
    except FileNotFoundError:
        raise
    except Exception as e:
        raise CheckpointCorrupt(
            f"{path}: unreadable archive ({type(e).__name__}: {e})") from e


def _get_array(data, key: str, path: str) -> np.ndarray:
    try:
        return data[key]
    except KeyError:
        raise CheckpointCorrupt(f"{path}: missing array {key!r}") from None
    except Exception as e:   # per-member CRC/zlib failure on decompress
        raise CheckpointCorrupt(
            f"{path}: array {key!r} unreadable "
            f"({type(e).__name__}: {e})") from e


def _verify_sums(data, sums: Dict[str, str], path: str) -> None:
    for key in sorted(sums):
        got = _sha256(_get_array(data, key, path))
        if got != sums[key]:
            raise CheckpointCorrupt(
                f"{path}: checksum mismatch for leaf {key!r} "
                f"(expected {sums[key][:12]}…, got {got[:12]}…) — shard is "
                f"truncated or bit-flipped")


def clean_stale_tmp(directory: str) -> List[str]:
    """Remove ``*.tmp`` directories left by saves that crashed before
    their atomic rename.  Returns the paths removed.  Safe to call on an
    open checkpoint dir as long as no save is in flight."""
    removed = []
    for tmp in glob.glob(os.path.join(directory, "*.tmp")):
        if os.path.isdir(tmp):
            shutil.rmtree(tmp, ignore_errors=True)
            removed.append(tmp)
    return removed


def _flatten_with_names(tree) -> Tuple[List[Tuple[str, Any]], Any]:
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        name = "/".join(_key_str(k) for k in path) or "_root"
        out.append((name, leaf))
    return out, treedef


def _key_str(k) -> str:
    if hasattr(k, "name"):
        return str(k.name)
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


# ---------------------------------------------------------------------------
# TT payload checkpointing (compressed wire format, Fig. 1 edge→cloud)
# ---------------------------------------------------------------------------
#
# A TTCompressor payload is a params-shaped pytree of CompressedParam
# leaves; saving it instead of the dense state keeps the checkpoint at the
# compressed size AND lets the serving side restore straight into TT-native
# mode (``models.common.tt_native_params``) without ever holding the dense
# weights.  Layout: one directory with
#     tt_manifest.json  — per-leaf kind/shape/dtype/ranks/eps/crop metadata
#     tt_payload.npz    — raw leaves + TT cores (cores keep their dtype)
#     _COMMITTED        — atomic commit marker

def save_tt_payload(directory: str, payload, extra: Optional[Dict] = None,
                    family: Optional[str] = None,
                    quant: Optional[str] = None,
                    quant_calib: str = "absmax") -> str:
    """Serialize a TTCompressor payload (CompressedParam pytree).

    family: the model family (``cfg.family``) the payload was compressed
    from, recorded in the manifest so a TT-native restore can select the
    right serving-rule set (and refuse a payload from the wrong arch).

    quant: integer storage format (``"int8"``) or None.  When set, TT cores
    are written symmetrically quantized (one scale per leading-rank row of
    each core, stored beside it as ``<key>__core<k>__scale``) — the on-disk payload shrinks ~4x on
    the cores.  ``load_tt_payload`` dequantizes back to the wide core dtype;
    the restored values sit exactly on the quantization grid, so a serving-
    side requantization (``tt_native_params(quant=...)`` with absmax
    calibration) reproduces the integer values and scales bit-identically —
    the round-trip is lossless relative to the quantized form."""
    from repro.core.compression import CompressedParam
    from repro.core import tt_linear as _ttl

    qdt = None if quant is None else _ttl.quant_dtype(quant)

    def is_cp(x):
        return isinstance(x, CompressedParam)

    flat, _ = jax.tree_util.tree_flatten_with_path(payload, is_leaf=is_cp)
    arrays: Dict[str, np.ndarray] = {}
    leaves = []
    for path, c in flat:
        name = "/".join(_key_str(k) for k in path) or "_root"
        if not is_cp(c):
            raise TypeError(f"{name}: not a CompressedParam leaf: {type(c)}")
        key = name.replace("/", "__")
        meta = {
            "name": name,
            "kind": c.kind,
            "orig_shape": list(c.orig_shape),
            "orig_dtype": str(jax.numpy.dtype(c.orig_dtype)),
            "crop_dims": list(c.crop_dims) if c.crop_dims else None,
        }
        if c.kind == "tt":
            meta["tt"] = {
                "shape": list(c.tt.shape),
                "ranks": [int(r) for r in c.tt.ranks],
                "eps": float(c.tt.eps),
                "core_dtypes": [str(g.dtype) for g in c.tt.cores],
            }
            if qdt is not None:
                meta["tt"]["quant"] = {"dtype": quant, "calib": quant_calib}
                for k, g in enumerate(c.tt.cores):
                    q, s = _ttl.quantize_core(
                        jax.numpy.asarray(g), dtype=qdt, calib=quant_calib
                    )
                    arrays[f"{key}__core{k}"] = np.asarray(jax.device_get(q))
                    arrays[f"{key}__core{k}__scale"] = np.asarray(
                        jax.device_get(s), np.float32
                    )
            else:
                for k, g in enumerate(c.tt.cores):
                    arrays[f"{key}__core{k}"] = np.asarray(
                        jax.device_get(g), np.float32
                    )
        else:
            # raw leaves round-trip through f32 (np lacks bf16/fp8 writers)
            arrays[f"{key}__raw"] = np.asarray(
                jax.device_get(jax.numpy.asarray(c.raw).astype(
                    jax.numpy.float32))
            )
        leaves.append(meta)

    tmp = directory + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)
    np.savez(os.path.join(tmp, "tt_payload.npz"), **arrays)
    manifest = {"time": time.time(), "leaves": leaves, "extra": extra or {},
                "family": family, "quant": quant,
                "sha256": {k: _sha256(v) for k, v in arrays.items()}}
    with open(os.path.join(tmp, "tt_manifest.json"), "w") as f:
        json.dump(manifest, f)
    with open(os.path.join(tmp, "_COMMITTED"), "w") as f:
        f.write("ok")
    # crash-safe swap: the previous committed payload is parked at .old (not
    # deleted) until the new one is in place; load_tt_payload falls back to
    # .old, so every crash window leaves at least one loadable payload
    old = directory + ".old"
    if os.path.exists(old):
        shutil.rmtree(old)
    if os.path.exists(directory):
        os.rename(directory, old)
    os.rename(tmp, directory)
    if os.path.exists(old):
        shutil.rmtree(old)
    return directory


def load_tt_payload(directory: str, like, verify: bool = True
                    ) -> Tuple[Any, Dict]:
    """Restore a TT payload into the tree structure of ``like`` (the params
    pytree the payload was compressed from, or any same-structure tree).

    ``verify=True`` (default) checks every array against the sha256 the
    manifest recorded at save time and raises ``CheckpointCorrupt`` naming
    the bad leaf; payloads written before checksums existed load without
    verification either way."""
    import jax.numpy as jnp

    from repro.core.compression import CompressedParam
    from repro.core import tt_linear as _ttl
    from repro.core.tt import TTTensor

    if not os.path.exists(os.path.join(directory, "_COMMITTED")):
        old = directory + ".old"        # interrupted save_tt_payload swap
        if os.path.exists(os.path.join(old, "_COMMITTED")):
            directory = old
        else:
            raise FileNotFoundError(f"no committed TT payload in {directory}")
    with open(os.path.join(directory, "tt_manifest.json")) as f:
        manifest = json.load(f)
    npz_path = os.path.join(directory, "tt_payload.npz")
    data = _load_npz(npz_path)
    if verify and manifest.get("sha256"):
        _verify_sums(data, manifest["sha256"], npz_path)

    named, treedef = _flatten_with_names(like)
    by_name = {m["name"]: m for m in manifest["leaves"]}
    missing = set(by_name) ^ {n for n, _ in named}
    if missing:   # leaves resolve by name, so ordering differences are fine
        raise ValueError(f"payload/tree structure mismatch: {sorted(missing)}")

    leaves = []
    for name, _ in named:
        m = by_name[name]
        key = name.replace("/", "__")
        dtype = jnp.dtype(m["orig_dtype"])
        crop = tuple(m["crop_dims"]) if m.get("crop_dims") else None
        if m["kind"] == "tt":
            quant = m["tt"].get("quant")
            cores = []
            for k, cd in enumerate(m["tt"]["core_dtypes"]):
                arr = data[f"{key}__core{k}"]
                if quant is not None:
                    # dequantize to the wide core dtype: restored values sit
                    # exactly on the integer grid, so requantizing at serve
                    # time (absmax) is bit-identical to what was saved
                    arr = np.asarray(_ttl.dequantize_core(
                        arr, data[f"{key}__core{k}__scale"]))
                cores.append(jnp.asarray(arr, jnp.dtype(cd)))
            tt = TTTensor(
                cores=cores, shape=tuple(m["tt"]["shape"]),
                ranks=tuple(m["tt"]["ranks"]), eps=m["tt"]["eps"],
            )
            leaves.append(CompressedParam(
                "tt", tt, None, tuple(m["orig_shape"]), dtype,
                crop_dims=crop,
            ))
        else:
            raw = jnp.asarray(data[f"{key}__raw"]).astype(dtype)
            leaves.append(CompressedParam(
                "raw", None, raw, tuple(m["orig_shape"]), dtype,
                crop_dims=crop,
            ))
    return jax.tree_util.tree_unflatten(treedef, leaves), manifest


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3, async_save: bool = True):
        self.dir = directory
        self.keep = keep
        self.async_save = async_save
        self._pending: Optional[threading.Thread] = None
        os.makedirs(directory, exist_ok=True)
        # a save that crashed before its atomic rename leaves step_*.tmp
        # behind; nothing references it, so reclaim the space on open
        self.cleaned_tmp = clean_stale_tmp(directory)

    # ---------------- save ----------------

    def save(self, step: int, state, extra: Optional[Dict] = None) -> str:
        """Snapshot state (device→host copy happens synchronously; disk write
        is async unless async_save=False — the paper's clock-gating analogue:
        I/O overlaps the next step's compute)."""
        self.wait()
        named, _ = _flatten_with_names(state)

        def to_host(v):
            a = np.asarray(jax.device_get(v))
            if a.dtype.kind not in "fiub":      # ml_dtypes (bf16/fp8) -> fp32
                a = np.asarray(jax.numpy.asarray(a).astype(np.float32))
            return a

        host = [(n, to_host(v)) for n, v in named]
        path = self._step_dir(step)

        def write():
            tmp = path + ".tmp"
            if os.path.exists(tmp):
                shutil.rmtree(tmp)
            os.makedirs(tmp, exist_ok=True)
            np.savez(os.path.join(tmp, "shard_0.npz"),
                     **{n.replace("/", "__"): v for n, v in host})
            manifest = {
                "step": step,
                "time": time.time(),
                "leaves": [
                    {"name": n, "shape": list(v.shape), "dtype": str(v.dtype),
                     "sha256": _sha256(v)}
                    for n, v in host
                ],
                "extra": extra or {},
            }
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump(manifest, f)
            with open(os.path.join(tmp, "_COMMITTED"), "w") as f:
                f.write("ok")
            if os.path.exists(path):
                shutil.rmtree(path)
            os.rename(tmp, path)
            self._gc()

        if self.async_save:
            self._pending = threading.Thread(target=write, daemon=True)
            self._pending.start()
        else:
            write()
        return path

    def wait(self):
        if self._pending is not None:
            self._pending.join()
            self._pending = None

    # ---------------- restore ----------------

    def latest_step(self) -> Optional[int]:
        steps = []
        for d in os.listdir(self.dir):
            if d.startswith("step_") and os.path.exists(
                os.path.join(self.dir, d, "_COMMITTED")
            ):
                steps.append(int(d.split("_")[1]))
        return max(steps) if steps else None

    def restore(self, state_like, step: Optional[int] = None,
                shardings=None, verify: bool = True) -> Tuple[Any, Dict]:
        """Restore into the structure of ``state_like``; apply ``shardings``
        (a matching pytree of NamedSharding) if given — this is where
        elastic mesh changes are absorbed.

        ``verify=True`` (default) re-hashes every shard array against the
        manifest's sha256 and raises ``CheckpointCorrupt`` naming the bad
        leaf; checkpoints from before checksums load unverified."""
        self.wait()
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint in {self.dir}")
        path = self._step_dir(step)
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        shard_path = os.path.join(path, "shard_0.npz")
        data = _load_npz(shard_path)
        if verify:
            sums = {m["name"].replace("/", "__"): m["sha256"]
                    for m in manifest["leaves"] if "sha256" in m}
            _verify_sums(data, sums, shard_path)
        named, treedef = _flatten_with_names(state_like)
        leaves = []
        sh_flat = None
        if shardings is not None:
            sh_named, _ = _flatten_with_names(shardings)
            sh_flat = [s for _, s in sh_named]
        for i, (n, like) in enumerate(named):
            arr = _get_array(data, n.replace("/", "__"), shard_path)
            # cast via jnp (numpy lacks cast kernels for bf16/fp8 ml_dtypes)
            if hasattr(like, "dtype") and arr.dtype != like.dtype:
                arr = np.asarray(jax.numpy.asarray(arr).astype(like.dtype))
            if sh_flat is not None:
                leaves.append(jax.device_put(arr, sh_flat[i]))
            else:
                leaves.append(jax.numpy.asarray(arr))
        return jax.tree_util.tree_unflatten(treedef, leaves), manifest

    # ---------------- misc ----------------

    def _step_dir(self, step: int) -> str:
        return os.path.join(self.dir, f"step_{step:06d}")

    def _gc(self):
        steps = sorted(
            int(d.split("_")[1]) for d in os.listdir(self.dir)
            if d.startswith("step_") and not d.endswith(".tmp")
        )
        for s in steps[: -self.keep] if self.keep else []:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)
