"""Dev test: Faces halo exchange on a 2x2x2 fake-device grid, ST vs host
executors, all throttling modes, vs a pure-numpy oracle."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import STStream, halo
from repro.launch.mesh import make_mesh

GRID = (2, 2, 2)
N = (4, 4, 4)
NITER = 3


def run(mode, throttle="adaptive", merged=True):
    mesh = make_mesh(GRID, ("x", "y", "z"))
    stream = STStream(mesh, ("x", "y", "z"))
    win = halo.create_faces_window(stream, N)
    state = stream.allocate()
    rng = np.random.RandomState(0)
    src0 = rng.rand(8, *N).astype(np.float32)
    state["faces.src"] = jax.device_put(
        jnp.asarray(src0), state["faces.src"].sharding)
    kernels = halo.make_faces_kernels(N)
    for it in range(NITER):
        halo.enqueue_faces_iteration(stream, win, N, kernels, merged=merged)
    state = stream.synchronize(state, mode=mode, throttle=throttle,
                               resources=16, merged=merged, donate=False)
    src_exp, acc_exp = halo.faces_reference(src0, GRID, N, NITER)
    np.testing.assert_allclose(np.asarray(state["faces.src"]), src_exp,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(state["faces.acc"]), acc_exp,
                               rtol=1e-5)
    # signal counters: every slot must equal NITER (epoch protocol ran)
    np.testing.assert_array_equal(np.asarray(state["faces.post_sig"]),
                                  NITER * np.ones((8, 26), np.int32))
    np.testing.assert_array_equal(np.asarray(state["faces.comp_sig"]),
                                  NITER * np.ones((8, 26), np.int32))
    print(f"OK mode={mode} throttle={throttle} merged={merged}")


if __name__ == "__main__":
    for merged in (True, False):
        for thr in ("adaptive", "static", "none"):
            run("st", thr, merged)
    run("host", merged=True)
    # merged=False drives the baseline's separate wire completion-signal
    # dispatches (backends.run_host unit="chained")
    run("host", merged=False)
