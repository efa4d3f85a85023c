"""AOT executable cache: pre-compile the bucket ladder, boot replicas warm.

``--warmup`` is *the* scale-out latency tax: every replica boot re-pays
full XLA compilation for the whole bucket ladder (seconds per program on
CPU, tens of seconds per program for the 512px TPU ladder) before it can
take traffic.  The programs are identical across replicas — same
weights, same shapes, same jaxlib — so the compile belongs OFFLINE:

    dptpu-aot --cache-dir CACHE --run-dir RUN      # once, anywhere
    dptpu-serve --run-dir RUN --warmup --aot-cache CACHE   # every boot

``build`` lowers + compiles each ladder program (``jax.jit(...)
.lower().compile()``), serializes the executable
(``jax.experimental.serialize_executable``) and writes one file per
program plus a manifest.  A warm boot deserializes instead of
compiling — CompileWatchdog-verified ZERO compiles — and installs each
executable into the predictor's per-shape AOT table
(:meth:`predict.Predictor.install_aot`).

Trust is explicit, never assumed:

* **the manifest is written atomically LAST** (the packed-data idiom:
  tmp + fsync + ``os.replace``) — a crashed build leaves NO manifest,
  never a half-trusted one;
* **every entry carries a crc32** over its serialized bytes, re-checked
  on every load (and by ``dptpu-aot --verify``): a torn or bit-rotted
  entry is a typed :class:`AotCacheError`, and the boot falls back
  LOUDLY to a fresh compile — degraded cold start, never a corrupt
  executable taking traffic;
* **the cache key is the full identity of the compiled program**:
  jax + jaxlib versions, platform, the live topology fingerprint
  (parallel/plan.topology_fingerprint — XLA executables are
  device-assignment-bound), resolution/channels/split shape, the
  quantization regime, and a digest of the served weights (the
  executable BAKES the params as constants, so an entry built from
  checkpoint A must never serve checkpoint B's boot).  Any mismatch is
  a typed :class:`AotCacheMiss` naming the differing keys — fresh
  compile, loud line, service boots anyway.

The deserialization gotcha (root-caused in analysis/ir.py): a
deserialized executable reports ZEROED memory stats, so anything that
audits or cost-models a program must do it from the LOWERED form at
build time — which is exactly what ``build`` does by sharing the
:mod:`telemetry.lowering` cache with jaxaudit, never from the
executable a warm boot loads.

TRUST BOUNDARY: the crc32 detects *rot* (torn writes, bit flips), not
*tampering* — entries deserialize via pickle, and the checksum lives in
the same directory as the bytes it covers, so anyone who can WRITE the
cache dir can execute code in every replica that boots from it.  Treat
the cache directory with exactly the trust you give the checkpoint
itself (same filesystem ACLs, same provenance); never point a boot at a
cache dir less trusted than the weights.
"""

from __future__ import annotations

import json
import os
import pickle
import sys
import zlib

import numpy as np

from ..chaos import sites as chaos_sites

MANIFEST = "manifest.json"

#: manifest schema version — bump on layout changes so an old cache
#: misses loudly instead of unpickling garbage
CACHE_VERSION = 1


class AotCacheMiss(KeyError):
    """No usable entry: absent cache/manifest/program, or a fingerprint
    mismatch (different jaxlib/topology/weights/...).  Expected in
    normal operation — the caller compiles fresh and says so."""

    def __str__(self) -> str:  # KeyError quotes its arg; keep it prose
        return self.args[0] if self.args else ""


class AotCacheError(RuntimeError):
    """A PRESENT entry that cannot be trusted: checksum mismatch, torn
    file, undeserializable payload.  The caller must fall back loudly —
    and never execute the bytes."""


def params_fingerprint(predictor) -> str:
    """sha256 over the served weight bytes (params + batch stats) — the
    piece of the cache key that pins WHICH checkpoint the executable
    baked.  Quantized trees digest their int8/scale buffers (QTensor is
    a pytree node), so f32 and int8 forms of one checkpoint never
    collide."""
    from ..train.checkpoint import param_digest

    return param_digest({"params": predictor.params,
                         "batch_stats": predictor.batch_stats})


def cache_fingerprint(predictor) -> dict:
    """The full identity a cache entry is only valid under.  Every field
    is load-bearing: executables are jaxlib-serialization-format-bound,
    platform- and device-assignment-bound, shape-bound, and bake the
    (possibly quantized) weights as constants."""
    import jax
    import jaxlib

    from ..parallel.plan import topology_fingerprint
    from .quantize import quantization_block

    return {
        "cache_version": CACHE_VERSION,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": jax.devices()[0].platform,
        "topology": topology_fingerprint(),
        "resolution": list(predictor.resolution),
        "in_channels": int(getattr(predictor, "in_channels", 4)),
        "split": bool(getattr(predictor, "supports_sessions", False)),
        "quantization": quantization_block(
            getattr(predictor, "quant_policy", None)),
        "params_digest": params_fingerprint(predictor),
    }


def fingerprint_mismatch(saved: dict, live: dict) -> list[str]:
    """The keys on which two fingerprints disagree (empty = compatible).
    Compared key-by-key so the miss message NAMES what moved — 'topology:
    cpu:8/p1 != tpu:4/p1' routes the operator straight to the fix."""
    keys = sorted(set(saved) | set(live))
    return [f"{k}: cached {saved.get(k)!r} != live {live.get(k)!r}"
            for k in keys if saved.get(k) != live.get(k)]


def ladder_programs(predictor, buckets) -> list[tuple]:
    """``[(name, fn, args, install_key), ...]`` — the bucket ladder's
    compiled-program inventory for one predictor (the same programs
    ``InferenceService.warmup`` compiles): per bucket, one whole
    forward for a stem predictor, encode + decode for a split one."""
    import jax
    import jax.numpy as jnp

    h, w = predictor.resolution
    ch = int(getattr(predictor, "in_channels", 4))
    sds = jax.ShapeDtypeStruct
    out = []
    if getattr(predictor, "supports_sessions", False):
        feats1 = predictor.feature_struct(1)
        for b in buckets:
            out.append((f"encode_b{b}", predictor.encode_jitted,
                        (sds((b, h, w, ch - 1), jnp.float32),),
                        ("encode", b)))
            out.append((f"decode_b{b}", predictor.decode_jitted,
                        (sds((b, *feats1.shape[1:]), feats1.dtype),
                         sds((b, h, w, 1), jnp.float32)),
                        ("decode", b)))
    else:
        for b in buckets:
            shape = (b, h, w, ch)
            out.append((f"forward_b{b}", predictor.forward_jitted,
                        (sds(shape, jnp.float32),), ("forward", shape)))
    return out


class AotCache:
    """One cache directory: entry files + the atomically-written manifest.

    ``verify`` and ``manifest`` are pure stdlib (zlib/json) — the
    ``dptpu-aot --verify`` sweep never initializes a jax backend.
    ``build``/``load`` touch jax (lower/compile, deserialize)."""

    def __init__(self, cache_dir: str):
        self.cache_dir = str(cache_dir)

    # ---------------------------------------------------------- manifest

    def manifest_path(self) -> str:
        return os.path.join(self.cache_dir, MANIFEST)

    def manifest(self) -> dict:
        """The parsed manifest.  Missing -> :class:`AotCacheMiss` (a
        cache that was never built, or whose build crashed pre-commit);
        unparseable -> :class:`AotCacheError` (the atomic write makes a
        torn manifest a corruption signal, not a crash artifact)."""
        try:
            with open(self.manifest_path(), encoding="utf-8") as f:
                raw = f.read()
        except OSError:
            raise AotCacheMiss(
                f"no AOT manifest at {self.manifest_path()} — build one "
                "with `dptpu-aot --cache-dir ...`") from None
        try:
            man = json.loads(raw)
            if not isinstance(man.get("entries"), dict) \
                    or not isinstance(man.get("fingerprint"), dict):
                raise ValueError("manifest missing entries/fingerprint")
            for name, ent in man["entries"].items():
                # schema-validate every entry record here, so a
                # valid-JSON-but-mangled manifest stays inside the typed
                # fallback contract (load/verify index into these fields
                # — an unvalidated TypeError there would escape the
                # warmup's miss/error handling and kill the boot)
                if (not isinstance(ent, dict)
                        or not isinstance(ent.get("file"), str)
                        or not isinstance(ent.get("bytes"), int)
                        or not isinstance(ent.get("crc32"), int)):
                    raise ValueError(
                        f"entry {name!r} malformed (want file/bytes/"
                        f"crc32, got {ent!r})")
        except ValueError as e:
            raise AotCacheError(
                f"unreadable AOT manifest {self.manifest_path()}: {e} — "
                "rebuild the cache") from None
        return man

    # ------------------------------------------------------------- build

    def build(self, predictor, buckets) -> dict:
        """Pre-compile + serialize the whole ladder; returns a summary.

        Lowers through the shared :mod:`telemetry.lowering` cache (one
        lower per program per process, shared with jaxaudit — the audit
        of these exact programs happens from the LOWERED form here, not
        from a deserialized executable whose memory stats are zeroed).
        """
        import jax
        from jax.experimental import serialize_executable

        from ..telemetry.lowering import lower_cached

        if getattr(predictor, "mesh", None) is not None:
            raise ValueError(
                "AotCache.build: mesh predictors compile GSPMD programs "
                "bound to this process's device assignment — the AOT "
                "cache serves single-device replicas")
        fingerprint = cache_fingerprint(predictor)
        os.makedirs(self.cache_dir, exist_ok=True)
        entries: dict[str, dict] = {}
        total = 0
        # THIS cache is the persistence layer: an executable that jax's
        # own persistent compilation cache deserialized re-serializes
        # into a blob that cannot deserialize again (its backend symbol
        # table is gone), so the build must compile genuinely fresh.
        # Flipping jax_enable_compilation_cache alone is NOT enough —
        # two jax-internal caches defeat it:
        #   1. compilation_cache.is_cache_used() LATCHES its answer at
        #      the first compile of the process; reset_cache() drops the
        #      latch so the disabled flag actually reaches the read path;
        #   2. Lowered.compile() consults an in-memory executable memo
        #      which may hold an executable an EARLIER (cache-enabled)
        #      compile deserialized from disk; clear_caches() drops it.
        # lower_cached's memo is our own and survives clear_caches(), so
        # lowering still shares the process-wide cache — only the
        # compile pays again.
        from jax._src import compilation_cache as _jax_cc

        cache_flag = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        _jax_cc.reset_cache()
        jax.clear_caches()
        try:
            for name, fn, args, _key in ladder_programs(predictor,
                                                        buckets):
                compiled = lower_cached(fn, *args).lowered.compile()
                payload, in_tree, out_tree = \
                    serialize_executable.serialize(compiled)
                try:
                    # round-trip proof at build time: a blob that cannot
                    # deserialize HERE would poison every warm boot; any
                    # residual cache-bypass leak must fail the build
                    serialize_executable.deserialize_and_load(
                        payload, in_tree, out_tree)
                except Exception as e:
                    raise AotCacheError(
                        f"freshly built executable {name!r} does not "
                        f"survive a serialization round-trip "
                        f"({type(e).__name__}: {e}) — refusing to "
                        "commit a cache no boot could load") from e
                blob = pickle.dumps((payload, in_tree, out_tree),
                                    protocol=pickle.HIGHEST_PROTOCOL)
                fname = f"{name}.exec"
                path = os.path.join(self.cache_dir, fname)
                tmp = path + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(blob)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, path)
                entries[name] = {"file": fname, "bytes": len(blob),
                                 "crc32": zlib.crc32(blob)}
                total += len(blob)
        finally:
            jax.config.update("jax_enable_compilation_cache",
                              cache_flag)
            # drop the latch again so the NEXT compile re-evaluates the
            # restored flag — without this, the build's disabled answer
            # would stay latched and the rest of the process would skip
            # the persistent cache entirely
            _jax_cc.reset_cache()
        # the manifest commits the cache as a unit, atomically and LAST
        # — a build that dies above leaves entry files but no manifest,
        # and a manifest-less directory is a MISS, never a half-trust
        from ..train.checkpoint import atomic_write_json

        atomic_write_json(self.manifest_path(),
                          {"version": CACHE_VERSION,
                           "fingerprint": fingerprint,
                           "entries": entries})
        return {"cache_dir": self.cache_dir,
                "programs": sorted(entries),
                "bytes": total,
                "fingerprint": fingerprint}

    # -------------------------------------------------------------- load

    def load(self, name: str, fingerprint: dict):
        """One entry -> a live ``jax.stages.Compiled``.

        Raises :class:`AotCacheMiss` (absent / fingerprint mismatch,
        message naming every differing key) or :class:`AotCacheError`
        (present but untrustworthy: crc mismatch, undeserializable).
        The ``serve/aot_load`` chaos seam fires on the raw bytes BEFORE
        the checksum gate — an injected bitflip must surface as the
        typed checksum failure, proving rot cannot reach execution."""
        from jax.experimental import serialize_executable

        man = self.manifest()
        mismatch = fingerprint_mismatch(man["fingerprint"], fingerprint)
        if mismatch:
            raise AotCacheMiss(
                "AOT cache fingerprint mismatch — the cached executables "
                "were built for a different "
                + "; ".join(mismatch))
        ent = man["entries"].get(name)
        if ent is None:
            raise AotCacheMiss(
                f"no cached executable for program {name!r} "
                f"(cache holds: {sorted(man['entries'])})")
        path = os.path.join(self.cache_dir, ent["file"])
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError as e:
            raise AotCacheMiss(
                f"cached executable file missing for {name!r}: {e}") \
                from None
        # chaos seam: bit rot between disk and deserialization.  The
        # payload rides as a uint8 view; a bitflip fault returns a
        # private flipped copy which the crc gate below MUST catch.
        arr = np.frombuffer(data, dtype=np.uint8)
        fired = chaos_sites.fire("serve/aot_load", payload=arr,
                                 name=name, path=path)
        if fired is not arr:
            data = fired.tobytes()
        if len(data) != int(ent["bytes"]) \
                or zlib.crc32(data) != int(ent["crc32"]):
            raise AotCacheError(
                f"checksum mismatch for cached executable {name!r} "
                f"({path}): {len(data)} bytes crc {zlib.crc32(data)} vs "
                f"manifest {ent['bytes']} bytes crc {ent['crc32']} — "
                "torn write or bit rot; rebuild with dptpu-aot (or "
                "delete the cache dir)")
        try:
            payload, in_tree, out_tree = pickle.loads(data)
            return serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree)
        except Exception as e:
            raise AotCacheError(
                f"cached executable {name!r} failed to deserialize "
                f"({type(e).__name__}: {e}) — stale serialization "
                "format or corruption; rebuild with dptpu-aot") from e

    # ------------------------------------------------------------ verify

    def verify(self) -> dict:
        """Re-checksum every entry (pure zlib — no jax, no backend).
        Returns ``{"entries": n, "bad": [...], "missing": [...]}``;
        ``bad`` names entries whose bytes no longer match their
        manifest crc, ``missing`` entries whose file is gone."""
        man = self.manifest()
        bad: list[str] = []
        missing: list[str] = []
        for name, ent in sorted(man["entries"].items()):
            path = os.path.join(self.cache_dir, ent["file"])
            try:
                with open(path, "rb") as f:
                    data = f.read()
            except OSError:
                missing.append(name)
                continue
            if len(data) != int(ent["bytes"]) \
                    or zlib.crc32(data) != int(ent["crc32"]):
                bad.append(name)
        return {"entries": len(man["entries"]), "bad": bad,
                "missing": missing,
                "fingerprint": man.get("fingerprint")}


# ------------------------------------------------------------------- CLI

def main(argv: list[str] | None = None, predictor=None) -> int:
    """``dptpu-aot``: build or verify an AOT executable cache.

    Build (default): ``dptpu-aot --cache-dir C --run-dir RUN
    [--max-batch 8] [--quantize int8]`` — pre-compiles the exact ladder
    ``dptpu-serve --run-dir RUN --max-batch 8 [--quantize int8]`` would
    compile at boot.  Verify: ``dptpu-aot --cache-dir C --verify``
    re-checksums every entry, exit non-zero naming bad ones (pure
    host-side sweep, safe on a box with no accelerator).

    ``predictor`` injects a prebuilt predictor (tests drive the build
    path without a training run on disk)."""
    import argparse

    parser = argparse.ArgumentParser(
        prog="dptpu-aot",
        description="Pre-compile (and verify) the serve bucket ladder's "
                    "AOT executable cache — near-zero cold start for "
                    "`dptpu-serve --warmup --aot-cache`.")
    parser.add_argument("--cache-dir", required=True,
                        help="cache directory (entry files + manifest)")
    parser.add_argument("--verify", action="store_true",
                        help="re-checksum every cache entry instead of "
                             "building; exit non-zero naming bad entries")
    src = parser.add_mutually_exclusive_group()
    src.add_argument("--run-dir",
                     help="training run dir to build the ladder from")
    src.add_argument("--torch", metavar="PTH",
                     help="torch state_dict checkpoint instead of a run")
    parser.add_argument("--max-batch", type=int, default=8,
                        help="top micro-batch bucket (power of two) — "
                             "must match the serving config")
    parser.add_argument("--quantize", choices=("int8", "none"),
                        default=None,
                        help="quantization regime to build for (default: "
                             "the run config's model.quantization)")
    args = parser.parse_args(argv)

    cache = AotCache(args.cache_dir)
    if args.verify:
        try:
            report = cache.verify()
        except (AotCacheMiss, AotCacheError) as e:
            print(f"dptpu-aot: {e}", file=sys.stderr)
            return 2
        print(json.dumps(report, indent=1, sort_keys=True))
        if report["bad"] or report["missing"]:
            print(f"dptpu-aot: {len(report['bad'])} corrupt + "
                  f"{len(report['missing'])} missing entr(ies): "
                  f"{report['bad'] + report['missing']} — rebuild the "
                  "cache (a serve boot would fall back to fresh "
                  "compiles)", file=sys.stderr)
            return 1
        print(f"dptpu-aot: {report['entries']} entr(ies) verified",
              file=sys.stderr)
        return 0

    if predictor is None:
        if not (args.run_dir or args.torch):
            parser.error("build needs --run-dir or --torch "
                         "(or pass --verify)")
        from .__main__ import build_predictor

        predictor = build_predictor(args)
    from .batching import bucket_sizes

    summary = cache.build(predictor, bucket_sizes(args.max_batch))
    print(json.dumps(summary, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
