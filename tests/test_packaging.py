"""Installability contract (reference: CMakeLists.txt + setup.py +
conda/ give the reference a reproducible install story; the TPU
package's story is `pip install -e . --no-deps --no-build-isolation`
in the zero-egress image, with a `pinned` extra recording the exact CI
versions)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_pyproject_declares_build_and_pins():
    try:
        import tomllib  # Python 3.11+
    except ModuleNotFoundError:
        import tomli as tomllib  # the 3.10 backport, same API

    with open(os.path.join(REPO, "pyproject.toml"), "rb") as f:
        meta = tomllib.load(f)
    assert meta["build-system"]["build-backend"] == "setuptools.build_meta"
    proj = meta["project"]
    assert proj["name"] == "flexflow-tpu"
    assert any(d.startswith("jax") for d in proj["dependencies"])
    pins = proj["optional-dependencies"]["pinned"]
    assert all("==" in p for p in pins), pins
    # the pins must match what this environment actually runs — a
    # drifted pin list is worse than none
    import importlib.metadata as md

    for pin in pins:
        name, ver = pin.split("==")
        try:
            installed = md.version(name)
        except md.PackageNotFoundError:
            # optional extras may be absent outside the pinned CI image
            continue
        assert installed == ver, (
            f"pin {pin} does not match installed {installed}")


def test_editable_wheel_metadata_builds():
    """PEP 660 editable metadata must be producible by the in-image
    setuptools — the actual `pip install -e .` path exercises exactly
    this hook (network-free)."""
    code = (
        "from setuptools import build_meta;"
        "import tempfile;"
        "print(bool(build_meta.prepare_metadata_for_build_editable("
        "tempfile.mkdtemp())))"
    )
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-1500:]
    assert "True" in r.stdout


def test_package_smoke_import():
    """The public surface imports from a clean interpreter with only
    the package root on sys.path (what an installed wheel provides)."""
    code = (
        "import flexflow_tpu as ff;"
        "m = ff.FFModel(ff.FFConfig(num_devices=1));"
        "assert hasattr(ff, 'AdamOptimizer') and hasattr(ff, 'MachineView');"
        "import flexflow_tpu.keras, flexflow_tpu.models;"
        "print('ok')"
    )
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], cwd="/tmp",
                       capture_output=True, text=True, timeout=240,
                       env=env)
    assert r.returncode == 0, r.stderr[-1500:]
    assert "ok" in r.stdout


def test_explicit_spmd_imports_shard_map_from_compat():
    """Every explicit-SPMD module imports shard_map from
    flexflow_tpu/comm/compat.py (one spelling of the replication
    checker, off by default), never from jax directly."""
    import ast

    pkg = os.path.join(REPO, "flexflow_tpu")
    allow = {os.path.join("comm", "compat.py")}  # the shim itself
    bad = []

    def _attr_path(node):
        parts = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if isinstance(node, ast.Name):
            parts.append(node.id)
        return ".".join(reversed(parts))

    for root, _dirs, files in os.walk(pkg):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            rel = os.path.relpath(path, pkg)
            if rel in allow:
                continue
            with open(path) as f:
                tree = ast.parse(f.read(), filename=rel)
            for node in ast.walk(tree):
                if isinstance(node, ast.ImportFrom):
                    mod = node.module or ""
                    if mod.split(".")[0] == "jax" and any(
                            a.name == "shard_map" for a in node.names):
                        bad.append(f"{rel}:{node.lineno}: "
                                   f"from {mod} import shard_map")
                elif isinstance(node, ast.Import):
                    for a in node.names:
                        if a.name.startswith("jax") and \
                                a.name.endswith("shard_map"):
                            bad.append(f"{rel}:{node.lineno}: "
                                       f"import {a.name}")
                elif isinstance(node, ast.Attribute):
                    dotted = _attr_path(node)
                    if dotted in ("jax.shard_map",
                                  "jax.experimental.shard_map",
                                  "jax.experimental.shard_map.shard_map"):
                        bad.append(f"{rel}:{node.lineno}: {dotted}")
    assert not bad, (
        "explicit-SPMD modules must import shard_map from "
        "flexflow_tpu.comm.compat, not jax directly:\n" + "\n".join(bad))
