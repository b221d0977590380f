"""Cluster-scale transparent pipelining (beyond-paper, DESIGN.md §3.3).

Plans the pipeline depth for a multi-pod deployment with the paper's
Eq.(6)/(7) math, then runs the actual GPipe schedule over a 4-way 'pod'
mesh (fake devices in a subprocess) and checks it against the sequential
execution.

Run:  PYTHONPATH=src python examples/pipeline_parallel.py
"""
import os
import subprocess
import sys
import textwrap

from repro.parallel import pipeline as cp


def main():
    print("=== pipeline-depth planning (Eq. 6/7 at pod scale) ===")
    for M in (4, 16, 64):
        c = cp.PipelineCost(n_pods=8, microbatches=M, layer_time_ms=2.0,
                            overhead_ms=0.5)
        p = cp.plan(c)
        print(f"  microbatches={M:3d}: collapse k={p['k']} "
              f"(k_hat={p['k_hat']:.2f}) -> {p['stages']} stages, "
              f"latency {p['latency_ms']:.1f}ms "
              f"(vs {p['latency_ms_k1']:.1f}ms at k=1), "
              f"bubble {p['bubble_fraction']*100:.0f}%")

    print("\n=== executing the GPipe schedule on a 4-pod mesh ===")
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        import sys; sys.path.insert(0, "src")
        from repro.parallel.pipeline import make_pipelined
        mesh = Mesh(np.asarray(jax.devices()).reshape(4), ("pod",))
        rng = np.random.RandomState(0)
        w = jnp.asarray(rng.randn(4, 16, 16) * 0.3, jnp.float32)
        x = jnp.asarray(rng.randn(8, 4, 16), jnp.float32)
        stage = lambda wi, h: jnp.tanh(h @ wi)
        piped = jax.jit(make_pipelined(stage, mesh))
        got = piped(w, x)
        want = x
        for i in range(4): want = jnp.tanh(want @ w[i])
        err = float(jnp.max(jnp.abs(got - want)))
        print(f"  4 stages x 8 microbatches: max err vs sequential {err:.2e}")
        assert err < 1e-5
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"    # never reach for an accelerator
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    print(out.stdout.strip() or out.stderr[-500:])
    assert "max err" in out.stdout
    print("example complete")


if __name__ == "__main__":
    main()
