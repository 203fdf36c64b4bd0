"""Worker process for the 2-process CPU multihost test (see
``test_multihost.py``). Argv: process_id num_processes coordinator_port."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# the 2-process test is a CPU simulation whatever the parent's environment says
jax.config.update("jax_platforms", "cpu")


def main() -> None:
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

    import numpy as np

    from perceiver_io_tpu.parallel import (
        MeshConfig,
        global_batch,
        initialize,
        is_multihost,
        make_mesh,
        shard_or_assemble,
    )

    initialize(
        coordinator_address=f"localhost:{port}", num_processes=nproc, process_id=pid
    )
    assert jax.process_count() == nproc, jax.process_count()
    assert jax.process_index() == pid
    assert is_multihost()
    n_local = len(jax.local_devices())
    assert jax.device_count() == nproc * n_local

    import jax.numpy as jnp

    mesh = make_mesh(MeshConfig(data=-1))

    # Each process contributes its own rows; the global array must see all.
    local = np.arange(2 * 3, dtype=np.float32).reshape(2, 3) + 100.0 * pid
    batch = global_batch({"x": local}, mesh)
    assert batch["x"].shape == (2 * nproc, 3), batch["x"].shape

    with mesh:
        total = jax.jit(jnp.sum)(batch["x"])
    expected = sum(
        float((np.arange(6, dtype=np.float32) + 100.0 * p).sum()) for p in range(nproc)
    )
    assert float(total) == expected, (float(total), expected)

    # The dispatcher must pick the multihost path.
    batch2 = shard_or_assemble({"x": local}, mesh)
    assert batch2["x"].shape == (2 * nproc, 3)

    # Fused multi-step blocks on a pod: leaves carry a leading (n_steps, ...)
    # dim; dim 1 is the per-host batch dim that gets assembled globally.
    k_steps = 3
    stacked_local = np.stack([local + 10.0 * s for s in range(k_steps)])
    stacked = global_batch({"x": stacked_local}, mesh, stacked_steps=True)
    assert stacked["x"].shape == (k_steps, 2 * nproc, 3), stacked["x"].shape
    with mesh:
        per_step = jax.jit(lambda x: jnp.sum(x, axis=(1, 2)))(stacked["x"])
    per_step = np.asarray(per_step)
    base = sum(
        float((np.arange(6, dtype=np.float32) + 100.0 * p).sum()) for p in range(nproc)
    )
    for s in range(k_steps):
        want = base + 10.0 * s * 6 * nproc  # +10/step on every element
        assert float(per_step[s]) == want, (s, float(per_step[s]), want)

    stacked2 = shard_or_assemble({"x": stacked_local}, mesh, stacked_steps=True)
    assert stacked2["x"].shape == (k_steps, 2 * nproc, 3)

    print(f"MULTIHOST_OK {pid} {float(total)}", flush=True)


if __name__ == "__main__":
    main()
