"""Paper Fig. 14(e,f): parallel QR scaling over the fabric.

The paper tiles PEs K x K on REDEFINE and shows near-linear speedup.  The
mesh analogue is the butterfly-tree TSQR: per-shard work drops linearly
with P while the tree adds log2(P) small (n x n) exchanges.  We measure
structural scaling (per-shard FLOPs, wire bytes, tree depth) exactly and
wall time on P fake CPU devices for reference (host cores bound it).
"""

import json
import subprocess
import sys
import textwrap


def _run_p(p: int, m: int, n: int) -> dict:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={p}"
        import time, json
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core.tsqr import tsqr_tree_sharded

        mesh = jax.make_mesh(({p},), ("data",))
        a = jnp.asarray(np.random.default_rng(0).standard_normal(({m}, {n})),
                        jnp.float32)
        f = jax.jit(jax.shard_map(lambda x: tsqr_tree_sharded(x, "data"),
                                  mesh=mesh, in_specs=P("data", None),
                                  out_specs=P()))
        r = f(a); jax.block_until_ready(r)
        t0 = time.perf_counter()
        for _ in range(3):
            r = f(a); jax.block_until_ready(r)
        dt = (time.perf_counter() - t0) / 3
        rounds = ({p}).bit_length() - 1
        local_flops = 2.0 * ({m} / {p}) * {n}**2 + rounds * 2.0 * (2*{n}) * {n}**2
        wire = rounds * {n} * {n} * 4
        print(json.dumps(dict(p={p}, wall_us=dt * 1e6,
                              local_flops=local_flops, wire_bytes=wire,
                              rounds=rounds)))
    """)
    # The child runs on an explicit CPU platform: it measures virtual-device
    # structure, and the parent may already hold the chip.
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600,
                         env={"PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
                              "PATH": "/usr/bin:/bin:/usr/local/bin"})
    return json.loads(res.stdout.strip().splitlines()[-1])


def run() -> list:
    rows = []
    m, n = 4096, 64
    base = None
    for p in (1, 2, 4, 8):
        try:
            r = _run_p(p, m, n)
        except Exception as e:  # pragma: no cover
            rows.append((f"fig14e_tsqr_p{p}", 0.0, f"error={e}"))
            continue
        if base is None:
            base = r["local_flops"]
        rows.append((f"fig14e_tsqr_p{p}", r["wall_us"],
                     f"flops_per_shard={r['local_flops']:.0f};"
                     f"work_speedup={base / r['local_flops']:.2f}x;"
                     f"wire_bytes={r['wire_bytes']};rounds={r['rounds']}"))
    return rows
