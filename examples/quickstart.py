"""Quickstart — the paper's Listing 1, verbatim flow.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

import sys
sys.path.insert(0, "src")

from repro.frontends import nn
from repro.frontends.optimize import optimize as sol_optimize
from repro.frontends.offload import device as sol_device
from repro.runtime import packed


def main() -> None:
    # 1. a normal framework model (the paper's py_model)
    py_model = nn.small_cnn(in_ch=3, classes=10)
    x = np.random.randn(1, 3, 32, 32).astype(np.float32)

    # 2. one line: extract → optimize → compile → inject   (paper line 5)
    sol_model = sol_optimize(py_model, (1, 3, 32, 32))

    # 3. parameters stay framework-managed                  (paper line 6)
    sol_model.load_state_dict(py_model.state_dict())

    # 4. run the optimized model                            (paper line 7)
    y = sol_model(x)
    y_ref = py_model(jnp.asarray(x))
    err = float(np.abs(np.asarray(y) - np.asarray(y_ref)).max())
    print(f"SOL output matches framework: max|Δ| = {err:.2e}")
    print(f"graph: {sol_model.stats()}")

    # 5. transparent offloading: pick a device once, inputs stay host-side
    sol_device.set("cpu", 0, mode="transparent")
    y2 = sol_model(x)
    print(f"transparent offload returns host array: {type(y2).__name__}, "
          f"transfers: {packed.TRANSFER_STATS}")


if __name__ == "__main__":
    main()
