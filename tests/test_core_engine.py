"""Core engine tests: autotuner, conv2d dispatch, single-image inference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get, tiny_variant
from repro.core import ConvSpec, InferenceEngine, conv2d, select
from repro.core.autotune import cost_model_select, measured_select
from repro.kernels import ref

KEY = jax.random.key(0)


def test_autotuner_picks_ilpm_on_paper_layers():
    """The cost model must reach the paper's conclusion on its own eval
    layers: ILP-M wins on bandwidth-limited single-image inference."""
    for h, c in [(56, 64), (28, 128), (14, 256)]:
        ch = select(ConvSpec(h=h, w=h, c=c, k=c))
        assert ch.algorithm == "ilpm", (h, c, ch)


def test_autotuner_feasibility_vmem():
    for h, c in [(56, 64), (7, 512)]:
        ch = cost_model_select(ConvSpec(h=h, w=h, c=c, k=c))
        assert ch.vmem <= 16 * 2 ** 20


def test_measured_select_runs():
    spec = ConvSpec(h=8, w=8, c=8, k=8)
    x = jax.random.normal(KEY, (1, 10, 10, 8))
    w = jax.random.normal(KEY, (3, 3, 8, 8))
    ch = measured_select(spec, x, w, repeats=1)
    assert ch.algorithm in ("ilpm", "direct", "im2col", "libdnn", "winograd")


@pytest.mark.parametrize("algorithm",
                         ["auto", "xla", "ilpm", "direct", "winograd"])
def test_conv2d_dispatch(algorithm):
    x = jax.random.normal(KEY, (1, 12, 12, 8))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (3, 3, 8, 16))
    y = conv2d(x, w, algorithm=algorithm)
    gt = ref.conv2d_reference(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(gt), rtol=2e-4,
                               atol=1e-3)


def test_conv2d_patch_embed_path():
    """Stride-p VALID pxp conv == non-overlapping ILP-M degenerate case."""
    x = jax.random.normal(KEY, (1, 28, 28, 3))
    w = jax.random.normal(jax.random.fold_in(KEY, 2), (14, 14, 3, 32))
    y = conv2d(x, w, stride=14, padding="VALID", algorithm="ilpm")
    gt = ref.conv2d_reference(x, w, stride=14, padding="VALID")
    assert y.shape == (1, 2, 2, 32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(gt), rtol=2e-4,
                               atol=1e-3)


def test_inference_engine_single_image():
    cfg = tiny_variant(get("resnet18"))
    eng = InferenceEngine(cfg)
    img = jax.random.normal(KEY, (32, 32, 3))
    logits = eng.run(img)
    assert logits.shape == (cfg.vocab_size,)
    assert not bool(jnp.isnan(logits).any())
    reports = eng.traffic_report()
    # every conv site: stem + 2 convs per basic block + the 1x1 projection
    # shortcut of each stage-entry block (stages 1..3 in the tiny config)
    assert len(reports) == 1 + 2 * sum(cfg.extra["blocks"]) + 3
    assert all(r.est_bytes > 0 for r in reports)
    # full backbone coverage: strided sites (stem 7x7/2, stage-entry 3x3/2,
    # 1x1/2 projections) run strided Pallas kernels, never the xla escape
    by_name = {r.name: r for r in reports}
    assert not [r.name for r in reports if r.algorithm == "xla"]
    assert by_name["stem"].algorithm in ("ilpm", "direct")
    assert by_name["s1b0.c1"].algorithm in ("ilpm", "direct")
    assert by_name["s1b0.proj"].algorithm == "pointwise"
    assert by_name["s0b0.c1"].algorithm in ("ilpm", "direct", "libdnn",
                                            "winograd", "im2col")
    assert by_name["s0b0.c1"].params


def test_engine_algorithms_agree():
    cfg = tiny_variant(get("resnet18"))
    img = jax.random.normal(KEY, (32, 32, 3))
    params = InferenceEngine(cfg).params
    outs = {}
    for algo in ("xla", "ilpm", "direct"):
        eng = InferenceEngine(cfg, params=params, algorithm=algo)
        outs[algo] = np.asarray(eng.run(img))
    np.testing.assert_allclose(outs["ilpm"], outs["xla"], rtol=1e-3,
                               atol=1e-3)
    np.testing.assert_allclose(outs["direct"], outs["xla"], rtol=1e-3,
                               atol=1e-3)


def test_vit_patch_embed_frontend():
    from repro.models import frontends
    from repro.models.spec import init_params

    cfg = tiny_variant(get("internvl2-26b"))
    p = init_params(frontends.vit_patch_specs(cfg, patch=7), 0, "float32")
    img = jax.random.normal(KEY, (1, 28, 28, 3))
    y = frontends.vit_patch_embed(p, cfg, img, patch=7)
    assert y.shape == (1, 16, cfg.d_model)


def test_audio_stem_frontend():
    from repro.models import frontends
    from repro.models.spec import init_params

    cfg = tiny_variant(get("whisper-base"))
    p = init_params(frontends.audio_stem_specs(cfg, n_mels=16), 0, "float32")
    mel = jax.random.normal(KEY, (1, 32, 16))
    y = frontends.audio_stem(p, cfg, mel)
    assert y.shape == (1, 16, cfg.d_model)  # stride-2 downsample


def test_init_params_same_weights_in_every_process():
    """A seed names the same weights in every process: the per-leaf keys
    must not depend on Python's per-process string-hash salt."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    import repro

    code = ("import jax, jax.numpy as jnp\n"
            "from repro.configs import get, tiny_variant\n"
            "from repro.models.registry import cnn_module\n"
            "from repro.models.spec import init_params\n"
            "cfg = tiny_variant(get('resnet18'))\n"
            "p = init_params(cnn_module(cfg).model_specs(cfg), 0, 'float32')\n"
            "print(repr([float(jnp.sum(jnp.abs(x)))"
            " for x in jax.tree.leaves(p)]))\n")
    src = str(Path(repro.__file__).resolve().parents[1])
    outs = set()
    for salt in ("1", "2"):
        env = {**os.environ, "PYTHONHASHSEED": salt, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": src}
        run = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300)
        assert run.returncode == 0, run.stderr[-2000:]
        outs.add(run.stdout)
    assert len(outs) == 1, outs
