"""The Qwen2 path pinned: the seeded weights, the reference's gaps and every
per-layer reading read what the benchmark read before its architecture
modules, when Qwen2 was built into the shared code (``data/qwen2.pinned.json``,
recorded then).  A change to a leaf's label, key, shape or scale, to the
reference layer, or to a count behind a reading moves one of these.

The weights and gaps come from XLA on the CPU, which may round the last bit
differently on another host's vector unit: they are held to a relative
1e-6 (sums of float32 leaves) and to the 1e-5 of the reference's own
blocking test; any real move changes them by far more.  The readings are
plain Python arithmetic on a recorded chip trace and are held to 1e-12."""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import BENCH, REPO, TINY_SIZES

from chipbench import harness, reference, roofline, weights, xtrace

PINNED = json.loads((Path(__file__).parent / "data" / "qwen2.pinned.json")
                    .read_text())
FIXTURE = Path(__file__).parent / "data" / "qwen2-0.5b.chat.xplane.pb"
SEED = PINNED["seed"]


def _conf(tiny=True):
    conf = json.loads((BENCH / "configs" / "qwen2-0.5b.json").read_text())
    if tiny:
        conf.update(TINY_SIZES)
    return conf, harness.load_arch(REPO / conf["reference"])


def test_qwen2_weights_are_pinned():
    import jax
    from repro.models import lm
    conf, arch = _conf()
    cfg = harness.model_config(conf, arch)
    expected = jax.eval_shape(lambda: lm.init_params(cfg,
                                                     jax.random.PRNGKey(0)))
    params = weights.program_params(expected, arch.dims(conf), SEED,
                                    cfg.padded_vocab, cfg.tie_embeddings,
                                    arch.LAYER_LEAVES)
    got = {p: np.asarray(a, np.float64) for p, a in weights._paths(params)}
    want = PINNED["params_sum_and_sumsq"]
    assert sorted(got) == sorted(want)
    for p, (total, sq) in want.items():
        assert got[p].sum() == pytest.approx(total, rel=1e-6, abs=1e-6), p
        assert (got[p] ** 2).sum() == pytest.approx(sq, rel=1e-6), p


def test_qwen2_reference_gaps_are_pinned():
    conf, arch = _conf()
    cfg = harness.model_config(conf, arch)
    rng = np.random.default_rng(0)
    served = [(rng.integers(0, 32768, n).tolist(),
               rng.integers(0, 32768, m).tolist())
              for n, m in ((5, 3), (40, 9), (300, 4), (2, 2))]
    got = reference.served_gaps(arch, arch.dims(conf), SEED, served,
                                padded_vocab=cfg.padded_vocab,
                                **arch.reference_kwargs(conf))
    assert len(got) == len(PINNED["gaps"])
    for g, want in zip(got, PINNED["gaps"]):
        np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-6)


def _recorded_ctx():
    """A fixed traced interval: the recorded qwen2-0.5b chat trace (one
    prefill and one decode dispatch of B 64, chunk 128) with fixed
    dispatch rows and engine counters."""
    conf, arch = _conf(tiny=False)
    stats = dict(prefill_tokens=265, prefill_dispatches=1, decode_tokens=61,
                 decode_dispatches=1, prefill_time_s=1.21, decode_time_s=0.33,
                 first_token_s=1.5, first_tokens=3, sample_time_s=0.0062,
                 step_time_s=1.56, steps=2, prefill_wait_s=1.19,
                 decode_wait_s=0.31)
    return SimpleNamespace(
        stats=stats, dims=arch.dims(conf), arch=arch,
        engine={"max_batch": 64, "prefill_chunk": 128},
        peak=roofline.peaks("TPU v5 lite", BENCH / "peaks.json"),
        trace=xtrace.reduce(xtrace.load(str(FIXTURE)), harness.KERNEL_EVENT),
        prefill_dispatches=[[(0, 97), (0, 128), (128, 40)]],
        decode_dispatches=[[300 + 7 * i for i in range(61)]])


@pytest.mark.parametrize("metric", sorted(PINNED["readings"]))
def test_qwen2_readings_are_pinned(metric):
    got = harness.load_reader(BENCH, metric)(_recorded_ctx())
    assert got == pytest.approx(PINNED["readings"][metric], rel=1e-12)
