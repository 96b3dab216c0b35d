"""The counts kept with the benchmark against the program's own.

  JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parent))

CONFIGS = sorted(p.stem for p in (HERE / "configs").glob("*.json"))


def load(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", CONFIGS)
def test_param_count_matches_num_params(name):
    from chip import counts, reference, system
    from repro.models.config import num_params

    cfg = load(name)
    assert counts.param_count(cfg) == (
        num_params(system.arch_config(cfg))
        + reference.family(cfg).not_in_num_params(cfg))


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_fit_the_program(name):
    """The benchmark's weights have the program's tree, and as many
    parameters as counted."""
    import jax

    from chip import counts, reference, system
    from repro.models import registry

    cfg = load(name)
    arch = system.arch_config(cfg)
    key = jax.random.PRNGKey(0)
    ours = jax.eval_shape(lambda k: reference.family(cfg).init(k, cfg), key)
    theirs = jax.eval_shape(
        lambda k: registry.get_model(arch).init(k, arch), key)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(ours)] == [
        (a.shape, a.dtype) for a in jax.tree.leaves(theirs)]
    assert sum(a.size for a in jax.tree.leaves(ours)) == counts.param_count(
        cfg)


def test_published_sizes():
    """Parameters per worker as the configurations are run."""
    from chip import counts

    assert counts.param_count(load("whisper-tiny")) == 56_355_840
    assert counts.param_count(load("mamba2-2.7b")) == 112_614_880


def test_peaks_refuse_unknown_kind():
    from chip import peaks

    assert peaks.peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
