"""The layer scopes of the round reach the compiled step.

`repro.dist.qgadmm` wraps each layer of a round in a named scope
"qgadmm.<layer>" (LAYERS); the optimized HLO keeps it in each
instruction's metadata, and benchmarks/chip/scopes.py joins a device
trace's ops to their layers through it.  Compiled here on the CPU at smoke
widths: the co-located W=2 step on the 8-bit and the f32 wire, and the
sharded W=4 step with the nibble-packed 4-bit wire on 4 virtual CPU
devices (in a subprocess, like tests/test_dist.py).  In the computations
the runtime runs as ops (not the bodies of fusions or of reductions), no
dot, convolution, custom call, collective or fusion carries more than one
layer token in its metadata, and at least 95% of them have a layer as
scopes.layer_map resolves it (XLA makes some wrappers with no metadata).
The Pallas kernels (here in interpret mode: the ops under their jitted
wrappers) sit under the codec or the exchange.  That the compiled kernels
keep the names the trace reduction finds is checked for a described v5e
in tests/test_tpu_compile.py.
"""
import os
import re
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.dist import qgadmm

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
from chip import scopes  # noqa: E402

# the jitted wrappers of the wire's Pallas kernels
KERNEL = re.compile(r"jit\((quantize|quantize_dequantize|pack4|unpack4)\)")
COUNTED = re.compile(r"^(dot|convolution|custom-call|fusion|all-gather|"
                     r"all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)")
TESTS = os.path.dirname(os.path.abspath(__file__))


def compiled_step(w: int, bits: int, quantize: bool, devices,
                  wire_impl: str = "pallas", **dist) -> str:
    """Optimized HLO of whisper-tiny's round at smoke widths: W workers on
    `devices` (fewer than W: the co-located step), the codec's Pallas
    kernels in interpret mode on a quantized wire; `dist` sets further
    DistConfig fields."""
    import jax.numpy as jnp

    from repro.core.gadmm import GADMMConfig
    from repro.core.quantizer import QuantizerConfig
    from repro.launch import train as launch
    from repro.models import registry

    cfg = registry.get_config("whisper-tiny", smoke=True)
    model = registry.get_model(cfg)
    dcfg = qgadmm.DistConfig(
        num_workers=w, gadmm=GADMMConfig(
            rho=1.0, quantize=quantize, qcfg=QuantizerConfig(bits=bits),
            alpha=0.01),
        local_iters=1, local_lr=1e-3,
        wire_impl=wire_impl if quantize else "jnp", **dist)
    mesh = launch.worker_mesh(np.asarray(devices), w)
    tr = qgadmm.QGADMMTrainer(model, cfg, dcfg, mesh)
    state = jax.eval_shape(lambda k: qgadmm.init_state(
        lambda kk: model.init(kk, cfg), k, dcfg), jax.random.PRNGKey(0))
    sds = jax.ShapeDtypeStruct
    batch = {"tokens": sds((w, 2, 16), jnp.int32),
             "labels": sds((w, 2, 16), jnp.int32),
             "frames": sds((w, 2, cfg.encoder_frames, cfg.d_model),
                           jnp.float32)}
    if mesh.shape["worker"] == w:
        step = tr.jit_train_step(state, batch)
    else:
        step = jax.jit(tr.make_train_step())
    return step.lower(state, batch).compile().as_text()


def census(hlo: str) -> dict:
    """The counted instructions of the computations run as ops: their own
    layer tokens, their resolved layer, and the layers of the Pallas
    kernels."""
    comps = scopes._computations(hlo)
    layers = scopes.layer_map(hlo)
    inner = set(re.findall(r"\b(?:calls|to_apply)=%([^\s,]+)", hlo))
    inner -= set(re.findall(r" call\(.*?to_apply=%([^\s,]+)", hlo))
    own, resolved, pallas = [], [], []
    for name, lines in comps.items():
        if name in inner:
            continue
        for line in lines:
            m = scopes.INSTRUCTION.match(line)
            if not COUNTED.match(m.group("op")):
                continue
            meta = scopes.METADATA.search(line)
            own.append(set(scopes.TOKEN.findall(meta.group(1)))
                       if meta else set())
            resolved.append(layers.get(m.group("name")))
            text = meta.group(1) if meta else "\n".join(
                comps.get(scopes.CALLS.search(line).group(1), [])
                if scopes.CALLS.search(line) else [])
            if KERNEL.search(text):
                pallas.append(resolved[-1])
    return {"own": own, "resolved": resolved, "pallas": pallas,
            "layers": set(resolved) - {None}}


def check(hlo: str, expect_pallas: bool) -> dict:
    c = census(hlo)
    n = len(c["resolved"])
    assert n > 100, n
    assert max(len(t) for t in c["own"]) == 1
    scoped = sum(1 for layer in c["resolved"] if layer)
    assert scoped / n >= 0.95, (scoped, n)
    assert c["layers"] <= set(qgadmm.LAYERS), c["layers"]
    if expect_pallas:
        assert c["pallas"], "no Pallas kernel in the step"
    assert set(c["pallas"]) <= {"codec", "exchange"}, c["pallas"]
    return c


def test_layer_names_are_fixed():
    assert qgadmm.LAYERS == ("local_solve", "codec", "exchange", "decode",
                             "dual", "metrics")
    with pytest.raises(ValueError):
        qgadmm._layer("local solve")


@pytest.mark.parametrize("wire", ["q8", "f32"])
def test_colocated_step_is_scoped(wire):
    hlo = compiled_step(2, 8, wire == "q8", jax.devices()[:1])
    c = check(hlo, expect_pallas=wire == "q8")
    assert {"local_solve", "codec", "decode", "dual",
            "metrics"} <= c["layers"], c["layers"]


@pytest.mark.parametrize("path", [
    "jacobi", "overlap", "staleness", "censor", "layerwise", "participation"])
def test_every_round_path_is_scoped(path):
    """Each way through the round reaches the scopes (8-bit wire, jnp
    codec, co-located W=2)."""
    from repro.core.censor import CensorConfig
    from repro.core.quantizer import LayerwiseConfig

    dist = {"jacobi": {"mode": "jacobi"}, "overlap": {"overlap": True},
            "staleness": {"staleness": 1},
            "censor": {"censor": CensorConfig(tau=1e-3, xi=0.9)},
            "layerwise": {"layerwise": LayerwiseConfig(periods=2)},
            "participation": {"participation": 0.5}}[path]
    hlo = compiled_step(2, 8, True, jax.devices()[:1], wire_impl="jnp",
                        **dist)
    c = check(hlo, expect_pallas=False)
    assert {"local_solve", "codec", "decode", "metrics"} <= c["layers"], \
        c["layers"]


def test_sharded_packed_step_is_scoped(tmp_path):
    out = tmp_path / "step.hlo.txt"
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {TESTS!r})
        import jax
        from test_layer_scopes import compiled_step
        assert len(jax.devices()) == 4
        open({str(out)!r}, "w").write(
            compiled_step(4, 4, True, jax.devices()))
        """)
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-4000:]
    hlo = out.read_text()
    c = check(hlo, expect_pallas=True)
    assert set(qgadmm.LAYERS) == c["layers"], c["layers"]
    assert {"codec", "exchange"} == set(c["pallas"]), c["pallas"]
    # the wire's collective-permutes belong to the exchange
    perms = [l for l in hlo.splitlines()
             if (m := scopes.INSTRUCTION.match(l))
             and m.group("op").startswith("collective-permute")]
    assert perms and all("qgadmm.exchange" in l for l in perms)
