"""Runtime/platform facts shared by the Pallas kernels and every entry
point: whether this process runs on the TPU (Mosaic-lowerable), the
per-core VMEM capacity, and the persistent-compilation-cache rule. One
definition each — the kernels' dispatch thresholds and the entry points
must never drift apart.
"""

from __future__ import annotations

import os

from raft_ncup_tpu.utils.knobs import knob_int

# Per-core VMEM capacity (~16 MiB on current TPUs —
# /opt/skills/guides/pallas_guide.md "Memory Hierarchy").
VMEM_BYTES = knob_int("RAFT_NCUP_VMEM_BYTES")

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
# Fixed: the directory is part of the cache key, so a path that moves
# (host fingerprint, pid, time, temp name) never hits.
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".cache", "xla")


def host_fingerprint() -> str:
    """Stable-ish host id (cpu model + core count, sha1/8). Cross-host
    CPU numbers differ >2x: a CPU figure is only comparable under one id."""
    import hashlib

    try:
        with open("/proc/cpuinfo") as f:
            model = next(
                (l.split(":", 1)[1].strip() for l in f if "model name" in l),
                "unknown",
            )
    except OSError:
        model = "unknown"
    raw = f"{model}|{os.cpu_count()}"
    return hashlib.sha1(raw.encode()).hexdigest()[:8]


def force_platform(platform: str) -> None:
    """Pin this process to ``platform`` (``--platform cpu`` on the CLIs,
    the tests' virtual CPU devices). Must run before the first device
    use. The env var is written for child processes, which inherit it;
    the config is written because ``jax`` read the env var when it was
    imported, which may have been before this call.

    Once a backend is initialised the config is read no more: a re-point
    to another platform then cannot apply, and says so instead of
    leaving the process on the backend it has (ADVICE round 5, found in
    ``__graft_entry__.py``, whose re-point moved here)."""
    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        have = jax.default_backend()
        if have != platform.split(",")[0]:
            raise RuntimeError(
                f"force_platform({platform!r}) cannot apply: this process "
                f"already initialised the {have!r} backend. Pin the "
                "platform before the first device use."
            )
    os.environ["JAX_PLATFORMS"] = platform
    jax.config.update("jax_platforms", platform)


def is_tpu_backend() -> bool:
    """Whether this process's default backend is the TPU (the only
    platform the Mosaic kernels compile for)."""
    import jax

    return jax.default_backend() == "tpu"


def enable_compilation_cache() -> str | None:
    """The one persistent-XLA-cache rule, called by every entry point
    before its first compile. Returns the directory in use, or ``None``
    when the cache is off.

    - ``JAX_COMPILATION_CACHE_DIR`` set: jax itself uses that directory;
      no directory is set in code.
    - unset, on an accelerator: ``<checkout>/.cache/xla``.
    - unset, on the CPU backend: off. Reloading cached entries of the
      fwd+bwd train program corrupted the glibc heap on the CPU test
      host, and the CPU only ever runs tests and rehearsals.

    Nothing ever deletes the cache."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if jax.default_backend() == "cpu":
            return None
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
