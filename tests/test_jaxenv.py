"""tony_tpu/utils/jaxenv.py: one place decides where compiled programs are
kept and how a process names its device."""

from __future__ import annotations

import re
from pathlib import Path

import jax
import pytest

from tony_tpu.utils import jaxenv

REPO = Path(__file__).resolve().parent.parent
ENTRY_POINTS = (
    "tony_tpu/cli/serve.py", "tony_tpu/examples/lm_train.py",
    "tony_tpu/examples/lm_generate.py", "tony_tpu/examples/mnist_jax.py",
    "tony_tpu/examples/warmup_mnist.py", "tony_tpu/warmpool.py",
    "chip_smoke.py",
)


def test_cache_placed_from_outside_sets_nothing_in_code(monkeypatch):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself; where it is set the
    helper must not touch the config at all."""
    monkeypatch.setenv(jaxenv.CACHE_ENV, "/somewhere/else")

    def refuse(*a, **k):
        raise AssertionError(f"config touched: {a}")
    monkeypatch.setattr(jax.config, "update", refuse)
    assert jaxenv.place_compile_cache() == "/somewhere/else"


def test_default_cache_is_one_fixed_directory_in_the_checkout(monkeypatch):
    """The path is part of the cache key: a directory built from a temp
    name, a pid or a time never hits."""
    monkeypatch.delenv(jaxenv.CACHE_ENV, raising=False)
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.append((k, v)))
    first = jaxenv.place_compile_cache()
    assert jaxenv.place_compile_cache() == first
    assert seen == [("jax_compilation_cache_dir", first)] * 2
    assert Path(first).parent == REPO
    ignored = (REPO / ".gitignore").read_text().split()
    assert Path(first).name + "/" in ignored


@pytest.mark.parametrize("path", ENTRY_POINTS)
def test_entry_point_places_the_cache_through_the_helper(path):
    src = (REPO / path).read_text()
    assert "place_compile_cache()" in src
    assert "jax_compilation_cache_dir" not in src


def test_no_other_code_sets_a_cache_directory():
    offenders = [
        str(p.relative_to(REPO))
        for p in [*REPO.glob("tony_tpu/**/*.py"), *REPO.glob("*.py")]
        if p.name != "jaxenv.py"
        and re.search(r"jax_compilation_cache_dir", p.read_text())]
    assert offenders == []


def test_device_report_names_the_device():
    rep = jaxenv.device_report()
    assert rep["platform"] == "cpu" and rep["count"] == len(jax.devices())
    assert isinstance(rep["kind"], str) and rep["kind"]
    # one entry per local device; CPU devices serve no allocator stats
    assert len(rep["bytes_in_use"]) == len(jax.local_devices())
