"""What the harness asks of the device and of compiled programs."""

from __future__ import annotations

import contextlib
import re
import shutil
import tempfile

from benchmarks.harness import trace_reduce
from benchmarks.harness.trace_reduce import instruction_name, op_family

MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
# one instruction of a compiled program's text, and its metadata's op_name
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%?[\w.\-]+ = .*)$", re.MULTILINE)
OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def mosaic_calls(compiled) -> int:
    """Mosaic (Pallas TPU) custom calls in a compiled program — what
    proves a kernel ran compiled, not interpreted or replaced."""
    return compiled.as_text().count(MOSAIC_TARGET)


def scopes_of(compiled) -> dict:
    """{instruction name: op_name} of every instruction in a compiled
    program's text.  The ``op_name`` of an instruction's metadata holds
    the ``jax.named_scope``s it was traced under
    (``jit(_raw_step)/transpose(jvp(ff.exit))/.../reduce_sum``); an
    instruction the compiler made without metadata maps to ``""``.  A
    profiler's device events carry instruction names only, so this is
    what charges device time to a scope (``readers.scope_time_share``)."""
    scopes = {}
    for text in INSTRUCTION.findall(compiled.as_text()):
        found = OP_NAME.search(text)
        scopes[instruction_name(text)] = found.group(1) if found else ""
    return scopes


def families_of(compiled) -> dict:
    """{instruction name: ``trace_reduce.op_family``} of the same text:
    a traced instruction whose family differs from the compiled one of
    its name came from ANOTHER program (``fusion.7`` is some fusion in
    every program), and ``readers.scope_time_share`` does not place it."""
    return {instruction_name(text): op_family(text)
            for text in INSTRUCTION.findall(compiled.as_text())}


def scope_facts(compiled) -> dict:
    """What a traced run's driver hands the scope readers among its
    facts, of the program it compiled after the window."""
    return {"scopes": scopes_of(compiled),
            "scope_families": families_of(compiled)}


def memory_analysis_bytes(compiled) -> dict:
    """XLA's own accounting of one program: arguments, outputs,
    temporaries (``memory_stats`` misses a step's temporaries)."""
    m = compiled.memory_analysis()
    return {"argument": m.argument_size_in_bytes,
            "output": m.output_size_in_bytes,
            "temp": m.temp_size_in_bytes,
            "alias": m.alias_size_in_bytes}


def memory_stats() -> list:
    import jax

    return [{k: (d.memory_stats() or {}).get(k)
             for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
            for d in jax.devices()]


class Tracer:
    """One short profiler capture inside a run, reduced in the run.  The
    Python tracer is off (it slows the host and swamps the file); host
    TraceAnnotation spans and the device planes stay on."""

    def __init__(self):
        self.dir = None

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def reduce(self):
        """The trace as ``trace_reduce.load`` gives it; the files go."""
        try:
            return trace_reduce.load_file(trace_reduce.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def annotation(name: str, on: bool):
    """A host span on the profiler's clock while ``on``; nothing else."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)
