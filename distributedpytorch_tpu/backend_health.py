"""Device policy: which platform a process runs on, whether its device
still answers, and where its compiles are cached.

``JAX_PLATFORMS`` from the environment is the only platform selector:
unset on a machine with a chip JAX takes the TPU; ``cpu`` is asked for by
name (the test suite, CPU smokes).  Nothing here probes the backend in a
child process or falls back to another platform: a chip belongs to one
process at a time, so a probe child would take it from its own parent,
and a measurement that finds no chip must fail rather than print a CPU
number (:func:`require_accelerator`).

Import-light on purpose (no jax/numpy at module scope): launchers import
this without initialising a backend.
"""

from __future__ import annotations

import os
import sys


def pin_cpu8_topology(env: dict | None = None) -> dict:
    """Pin the canonical 8-device CPU topology (tests/conftest.py's) into
    ``env`` (default ``os.environ``) BEFORE jax initializes — the one
    owner of the rule standalone CLIs (jaxaudit, dptpu-chaos) and chaos
    child processes share.  A no-op when jax is already imported (the
    process owns its topology) or when the caller pinned another
    platform (``JAX_PLATFORMS=tpu jaxaudit update``).  Returns ``env``.
    """
    if env is None:
        if "jax" in sys.modules:
            return os.environ
        env = os.environ
    plat = env.get("JAX_PLATFORMS", "")
    if plat and plat != "cpu":
        return env
    env.setdefault("JAX_PLATFORMS", "cpu")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    return env


def require_accelerator(what: str) -> str:
    """The platform a measuring entry point may run on: ``"tpu"``, or
    ``"cpu"`` only when ``JAX_PLATFORMS=cpu`` asked for it by name (the
    record then says ``platform: cpu`` and carries no MFU).  Anything else
    exits non-zero before a record can print — JAX falls back to the CPU
    quietly when it finds no chip, and a quiet fallback is how a CPU
    number ends up filed as a device number."""
    import jax

    platform = jax.devices()[0].platform
    if platform == "tpu" or (
            platform == "cpu" and os.environ.get("JAX_PLATFORMS") == "cpu"):
        return platform
    raise SystemExit(
        f"{what}: JAX found no TPU (platform {platform!r}) — refusing to "
        "measure; set JAX_PLATFORMS=cpu to ask for the CPU smoke by name")


def device_op_alive(timeout_s: float = 5.0) -> tuple[bool, str]:
    """In-process liveness: one trivial device computation, hard-bounded.

    A liveness endpoint polled every few seconds needs the question "can
    THIS process still run device work right now" answered in
    milliseconds.  The op runs on a daemon thread with a join
    timeout, so a wedged runtime yields ``(False, reason)`` instead of
    hanging the probe (the stuck daemon thread is abandoned — acceptable
    for a process whose orchestrator is about to restart it anyway).

    Returns ``(alive, reason)``; reason is empty when alive.
    """
    from .chaos.policies import PolicyTimeoutError, Timeout

    def run() -> float:
        import jax

        # tiny but real: touches dispatch, device math, and D2H
        return float(jax.device_get(
            jax.numpy.ones(()) + jax.numpy.ones(())))

    try:
        # daemon-thread timeout (chaos/policies): a wedged runtime yields
        # (False, reason) and the stuck worker is abandoned, exactly the
        # hand-rolled semantics this helper had before the consolidation
        value = Timeout(timeout_s).call(run)
    except PolicyTimeoutError:
        return False, f"device op exceeded {timeout_s}s"
    except KeyboardInterrupt:
        # Ctrl-C lands in the CALLER's frame (Timeout's join), not the
        # probe — the user is aborting the process, not the backend dying
        raise
    except BaseException as e:  # noqa: BLE001 — ANY probe failure means
        # dead: Timeout.call re-raises even SystemExit from a plugin's
        # init, and a probe must report (False, why), never crash serving
        return False, f"{type(e).__name__}: {e}"
    if value != 2.0:
        return False, f"device op returned {value!r}, not 2.0"
    return True, ""


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    The directory is placed from outside: where ``JAX_COMPILATION_CACHE_DIR``
    is set JAX already reads it and nothing is set in code; otherwise it is
    ``<checkout>/.jax_cache`` (a fixed path, so consecutive runs of one
    checkout share it).  One owner for every entry point that compiles.
    Call after ``import jax`` and before the first compilation."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
