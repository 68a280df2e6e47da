"""The platform probe: which engine runs the device phases of a query.

``platform()`` asks once per process which backend JAX runs on, and every
engine choice reads that one answer:

* ``plan/search.py`` pins ``engine()`` for the ``summarize`` (GFJS
  generation) and ``desummarize`` phases — ``"jax"`` on a TPU, ``"numpy"``
  anywhere else, where the Pallas kernels could only run interpreted;
* ``kernels/ops.py`` compiles the kernels (interpret mode off) exactly when
  the platform is ``"tpu"``.

``JoinService`` probes when it is constructed; ``GraphicalJoin`` and
``Executor`` reach the same cached answer when they plan on their own.  So
the engine never depends on which module happened to import jax first.

Where ``JAX_PLATFORMS`` names no TPU (``JAX_PLATFORMS=cpu``, the test
setting) the answer comes from the environment and jax is not imported:
planning stays jax-free there.  Where it names a TPU and JAX comes up on
something else, the probe raises instead of carrying on on the host.

On a TPU the probe also points JAX's persistent compilation cache at one
directory: ``JAX_COMPILATION_CACHE_DIR`` when set, else ``CACHE_DIR`` — a
fixed path inside the checkout (the path is part of each cache key, so a
moving directory never hits).  This is the only place the repository sets
it.

The TPU probe also registers :func:`on_compile` with ``jax.monitoring``,
so that each backend compile made while a tracer is active is recorded as
a ``jax:compile`` span.

Host fallbacks — a phase planned for the device that ran on the host — are
counted in the process registry under ``engine.host_fallback.<reason>``
(:func:`count_host_fallback`, read back by :func:`host_fallbacks`).
"""

from __future__ import annotations

import functools
import os
from pathlib import Path
from typing import Dict

from repro.obs.metrics import REGISTRY
from repro.obs.trace import ambient_tracer

#: the compile cache when JAX_COMPILATION_CACHE_DIR is unset
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"
HOST_FALLBACK = "engine.host_fallback"
#: the jax.monitoring event that reports each backend compile's duration
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@functools.cache
def platform() -> str:
    """The JAX backend this process runs on ("tpu", "cpu", ...)."""
    named = [p.strip() for p in os.environ.get("JAX_PLATFORMS", "").split(",")
             if p.strip()]
    if named and "tpu" not in named:
        return named[0]
    import jax
    name = jax.default_backend()
    if name == "tpu":
        _use_compilation_cache(jax)
        _listen_for_compiles(jax)
    elif named:
        raise RuntimeError(
            f"JAX_PLATFORMS={os.environ['JAX_PLATFORMS']!r} asks for a TPU "
            f"but JAX came up on {name!r}")
    return name


def engine() -> str:
    """Engine for the summarize/desummarize phases: "jax" only on a TPU."""
    return "jax" if platform() == "tpu" else "numpy"


def _use_compilation_cache(jax) -> None:
    """Persistent compile cache for the bucketed device programs.

    Every one of them is cached (minimum compile time 0): a served query
    compiles one program per padding bucket and kernel, and many of them
    finish under JAX's default one-second threshold.
    """
    jax.config.update(
        "jax_compilation_cache_dir",
        os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


@functools.cache
def _listen_for_compiles(jax) -> None:
    """Hand every backend compile to :func:`on_compile` (once a process)."""
    jax.monitoring.register_event_duration_secs_listener(on_compile)


def on_compile(event: str, duration: float, **kw) -> None:
    """A finished backend compile: under an active tracer, a ``jax:compile``
    span over the ``duration`` seconds that just ended, so that device-idle
    time spent compiling is charged to it."""
    if event != COMPILE_EVENT:
        return
    tracer = ambient_tracer()
    if tracer is not None:
        now = tracer.clock()
        tracer.add("jax:compile", now - duration, now, cat="jax")


def count_host_fallback(reason: str) -> None:
    """Record that a phase planned for the device ran on the host."""
    REGISTRY.counter(f"{HOST_FALLBACK}.{reason}").inc()


def host_fallbacks() -> Dict[str, int]:
    """reason -> count of host fallbacks recorded so far."""
    prefix = HOST_FALLBACK + "."
    return {name[len(prefix):]: int(s["value"])
            for name, s in REGISTRY.snapshot().items()
            if name.startswith(prefix)}
