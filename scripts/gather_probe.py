"""Clean gather-rate probe: marginal ns/row vs row width, many
dispatches per readback and two index counts to separate fixed from
marginal cost. Diagnostics only; needs the chip."""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench(fn, args, iters=16, reps=5, warm=2):
    import jax

    for _ in range(warm):
        np.asarray(fn(*args))
    best = []
    for _ in range(reps):
        t0 = time.perf_counter()
        outs = [fn(*args) for _ in range(iters)]
        np.asarray(outs[-1])
        best.append((time.perf_counter() - t0) * 1000 / iters)
    return float(np.median(best))


def main():
    import jax
    import jax.numpy as jnp

    from emqx_tpu.profiling import enable_compile_cache
    enable_compile_cache()
    print("backend:", jax.default_backend(), jax.devices(), flush=True)
    rng = np.random.default_rng(0)
    NB = 1 << 21
    NS = [1 << 19, 1 << 21]
    rows = {}
    for width in (4, 8, 12, 16, 24, 32, 48, 64, 96, 128, 192, 256):
        tbl = jax.device_put(
            rng.integers(0, 100, size=(NB, width), dtype=np.int32))
        per_n = {}
        for n_idx in NS:
            idx = jax.device_put(
                rng.integers(0, NB, size=(n_idx,), dtype=np.int32))
            f = jax.jit(lambda t, i: jnp.sum(t[i], dtype=jnp.int32))
            per_n[n_idx] = bench(f, (tbl, idx))
        marg = (per_n[NS[1]] - per_n[NS[0]]) / (NS[1] - NS[0]) * 1e6
        rows[width] = (per_n, marg)
        print(f"width={width:4d}: "
              + " ".join(f"n={n}: {ms:7.3f}ms" for n, ms in per_n.items())
              + f"  marginal={marg:6.2f} ns/row", flush=True)
    # 2D-index gather (the match kernel's [B, K] lane shape)
    width = 104
    tbl = jax.device_put(
        rng.integers(0, 100, size=(NB, width), dtype=np.int32))
    for bk in ((1 << 17, 4), (1 << 19, 4)):
        b, k = bk
        idx = jax.device_put(
            rng.integers(0, NB, size=(b, k), dtype=np.int32))
        f = jax.jit(lambda t, i: jnp.sum(t[i], dtype=jnp.int32))
        ms = bench(f, (tbl, idx))
        print(f"2D width={width} [{b}x{k}]: {ms:7.3f}ms "
              f"({ms * 1e6 / (b * k):6.2f} ns/row)", flush=True)


if __name__ == "__main__":
    main()
