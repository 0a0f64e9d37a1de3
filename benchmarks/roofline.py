"""Analytic roofline for the Pallas probe kernels, on the chip's peaks.

:func:`kernel_table` covers the simulator's own kernels — the
standalone ``ata_tag_probe`` *and* the fused ``ata_probe_rank``
(probe + winner rank + port arbitration) — with an analytic roofline
derived from their BlockSpecs: HBM bytes actually moved per call
(the tag state's block index never changes, so it is read once per
call; request columns and outputs stream once), integer VPU ops,
arithmetic intensity, and the memory/compute-bound time on the device
the run finds. Peaks come from :data:`PEAKS`, keyed by
``jax.devices()[0].device_kind``; a device missing from the table is
an error, never a default. Wall time is measured with the compiled
kernel (wrapper and kernel jitted as one executable, as the simulator
runs it), so the table needs a TPU.
"""
import jax

#: Published per-chip peaks, keyed by ``device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (system architecture):
#: 819 GB/s HBM bandwidth, 197 TFLOP/s bf16, 393 TOP/s int8.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12,
                    "int8_ops": 393e12},
}

#: Canonical probe-kernel shape (matches benchmarks.kernel_micro):
#: R requests against C caches of S sets x W ways, clusters of G.
KERNEL_SHAPE = {"R": 1024, "C": 16, "S": 8, "W": 64, "G": 4}


def device_peaks() -> dict:
    """The :data:`PEAKS` row of the device this process runs on."""
    kind = jax.devices()[0].device_kind
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[kind]


def kernel_model(name, shape=None):
    """Analytic (bytes, int_ops) per call for a probe kernel.

    Traffic follows the kernel BlockSpecs, not the array sizes: both
    kernels map the (int32) tag state to one block whose index is the
    same at every grid step, so Pallas copies it into VMEM once per
    call, while request columns and outputs stream once per tile. Ops
    count the set selector (2 selects per (request, cache, set, way):
    tag + line state) plus the comparator group and per-request
    reductions.
    """
    s = dict(KERNEL_SHAPE, **(shape or {}))
    R, C, S, W = s["R"], s["C"], s["S"], s["W"]
    state = C * S * W
    if name == "ata_tag_probe":
        bytes_ = (state * (4 + 4)                  # tags + valid
                  + R * 8                          # set_idx + qtag
                  + R * C * 8)                     # hits + ways out
        ops = R * C * W * (2 * S + 3)
    elif name == "ata_probe_rank":
        bytes_ = (state * (4 + 4)                  # tags + line state
                  + R * 24                         # 6 request columns in
                  + R * 20 + C * 4)                # 5 outputs + counts
        # probe over the full cluster + winner one-hot rank + the
        # grid-carried port-arbitration prefix counts
        ops = R * C * W * (2 * S + 3) + R * C * (s["G"] + 6)
    else:
        raise ValueError(f"unknown kernel {name!r}")
    return bytes_, ops


def kernel_table(shape=None):
    """Rows: (kernel, bytes, ops, intensity, mem_s, comp_s, bound,
    measured_us).

    The compute bound divides by the published int8 peak, an upper
    bound the kernels' int32 VPU compares cannot reach; the memory
    bound uses the HBM peak.
    """
    peaks = device_peaks()
    rows = []
    for name in ("ata_tag_probe", "ata_probe_rank"):
        bytes_, ops = kernel_model(name, shape)
        mem_s = bytes_ / peaks["hbm_bytes_per_s"]
        comp_s = ops / peaks["int8_ops"]
        bound = "memory" if mem_s >= comp_s else "compute"
        measured = _time_kernel(name, shape)
        rows.append((name, bytes_, ops, ops / bytes_, mem_s, comp_s,
                     bound, measured))
    return rows


def _time_kernel(name, shape=None, iters=20):
    """Median wall us/call of the compiled Pallas kernel (TPU only),
    wrapper and kernel timed as one jitted executable."""
    import time

    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    s = dict(KERNEL_SHAPE, **(shape or {}))
    R, C, S, W, G = s["R"], s["C"], s["S"], s["W"], s["G"]
    rng = np.random.default_rng(0)
    tags = jnp.asarray(rng.integers(0, 4096, (C, S, W)), jnp.int32)
    valid = jnp.asarray(rng.random((C, S, W)) < 0.7)
    qtag = jnp.asarray(rng.integers(0, 4096, R), jnp.int32)
    set_idx = jnp.asarray(rng.integers(0, S, R), jnp.int32)
    if name == "ata_tag_probe":
        args = (set_idx, qtag, tags, valid)

        def fn(*a):
            return ops.ata_probe(*a, impl="pallas")
    else:
        core = jnp.asarray(rng.integers(0, C, R), jnp.int32)
        cbase = (core // G) * G
        deny = jnp.asarray(rng.random(R) < 0.2)
        dirty = jnp.asarray(valid & (rng.random((C, S, W)) < 0.2))
        args = (set_idx, qtag, core, cbase, deny, tags, valid, dirty)

        def fn(*a):
            return ops.ata_probe_rank(*a, cluster_size=G, impl="pallas")
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2] * 1e6


def print_kernel_table(shape=None):
    s = dict(KERNEL_SHAPE, **(shape or {}))
    print(f"\n=== roofline: probe kernels (R={s['R']} C={s['C']} "
          f"S={s['S']} W={s['W']}) ===")
    print(f"{'kernel':16s} {'KB':>8s} {'ops':>10s} {'ops/B':>6s} "
          f"{'mem_us':>8s} {'comp_us':>8s} {'bound':8s} {'meas_us':>8s}")
    for name, b, o, ai, mem_s, comp_s, bound, meas in kernel_table(shape):
        print(f"{name:16s} {b / 1024:>8.1f} {o:>10d} {ai:>6.1f} "
              f"{mem_s * 1e6:>8.2f} {comp_s * 1e6:>8.2f} {bound:8s} "
              f"{meas:>8.1f}")


def main():
    print_kernel_table()


if __name__ == "__main__":
    main()
