"""A test-only architecture module: Qwen2 with no bias on the q, k and v
projections and the ``head_dim`` its configuration file states.  Those are
two of the ways Qwen3 departs from Qwen2; the tests drive the program's
qwen2-0.5b config with both, through the harness, as new files and an entry
alone.  Everything else is Qwen2's own module (``archs/qwen2.py``), found
beside this file in the checkout or in a test's tiny home."""
from pathlib import Path

from chipbench.harness import load_arch

_qwen2 = load_arch(Path(__file__).resolve().parents[2] / "benchmarks"
                   / "chip" / "archs" / "qwen2.py")

dims = _qwen2.dims                       # head_dim as the file states it
reference_kwargs = _qwen2.reference_kwargs
kernel_calls_per_dispatch = _qwen2.kernel_calls_per_dispatch
step_least_time = _qwen2.step_least_time
model_flops = _qwen2.model_flops

LAYER_LEAVES = {lb: fn for lb, fn in _qwen2.LAYER_LEAVES.items()
                if not lb.endswith("/b")}
_NO_BIAS = {f"attn/{p}/b": 0.0 for p in ("wq", "wk", "wv")}


def program_kwargs(conf):
    return dict(_qwen2.program_kwargs(conf), qkv_bias=False)


def layer(x, w, **kw):
    """Qwen2's layer with the q, k and v biases absent (added as 0)."""
    return _qwen2.layer(x, {**w, **_NO_BIAS}, **kw)
