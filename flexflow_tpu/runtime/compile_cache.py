"""Where jax keeps its persistent compilation cache, and what jax
compiled, loaded from it or traced again.

Every chip call starts cold, and the train step alone compiles for
tens of seconds, so each entry script calls ``place_compile_cache``
first thing.  The directory is part of nothing the program computes,
but it has to be the SAME directory run after run for an entry to be
found again: a fixed path inside the checkout, unless the machine says
otherwise through ``JAX_COMPILATION_CACHE_DIR``.
"""

from __future__ import annotations

import os

from flexflow_tpu.obs.metrics import METRICS

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Point jax's persistent compilation cache at a directory and
    return it.  With ``JAX_COMPILATION_CACHE_DIR`` set nothing is
    touched — jax reads the variable itself; otherwise the cache is
    ``<checkout>/.jax_cache`` (gitignored).  Call before the first
    compile."""
    watch_jax_compiles()
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# jax.monitoring duration events -> the registry's histograms.  The
# backend event wraps ``compile_or_get_cached``, so it counts a load from
# the persistent cache as well as a compile
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower_s",
    _BACKEND_COMPILE: "jax.backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "jax.cache_retrieval_s",
}
_EVENTS = {
    "/jax/compilation_cache/cache_hits": "jax.cache_hits",
    "/jax/compilation_cache/cache_misses": "jax.cache_misses",
}
_REQUESTS = "jax.compile_requests"
# the registry's ``name|key=value,key=value`` form reserves these
_LABEL_SAFE = str.maketrans({",": "_", "=": "_", "|": "_", '"': "'"})
_hists: dict = {}  # event -> its Histogram, filled by watch_jax_compiles


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    hist = _hists.get(event)  # the trace event fires thousands of times a run
    if hist is None:
        return
    hist.observe(duration_secs)
    if event == _BACKEND_COMPILE:
        # which program was compiled or loaded, and how often: jax hands
        # every duration listener the jitted function's name
        METRICS.counter(_REQUESTS).inc()
        fun = str(kw.get("fun_name", "?")).translate(_LABEL_SAFE)
        METRICS.counter(f"{_REQUESTS}|fun={fun}").inc()


def _on_event(event: str, **kw) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        METRICS.counter(name).inc()


def watch_jax_compiles() -> None:
    """Feed ``METRICS`` from ``jax.monitoring``: histograms
    ``jax.trace_s`` / ``jax.lower_s`` / ``jax.backend_compile_s`` /
    ``jax.cache_retrieval_s``, counters ``jax.compile_requests`` (total
    and ``|fun=<jitted function>``), ``jax.cache_hits``,
    ``jax.cache_misses`` — the answer to "which step recompiled".
    Registers once however often it is called; a listener runs only
    when jax compiles, never on the steady path."""
    if _hists:
        return
    from jax import monitoring

    _hists.update((event, METRICS.histogram(name))
                  for event, name in _DURATIONS.items())
    # the totals exist from now on, so that "no miss" reads 0, not absent
    for name in (_REQUESTS, *_EVENTS.values()):
        METRICS.counter(name)

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
