"""Offloading strategies (paper Sec. V).

**Transparent offloading**: ``sol.device.set(DEVICE, IDX)`` once; inputs
live on the host; SOL notices the placement mismatch, stages inputs/params
to the target device (packed transfers for many small tensors), runs there,
returns host outputs.  The framework never learns the device exists.
Params are cached in an offloading context (see SolModel) — great for
inference, pays gradient round-trips in training.

**Native offloading**: SOL shares the framework's device memory space —
params are already framework-device buffers; no staging, no copies; the
optimizer update runs device-side.  (The paper's PyTorch-dispatch-table
registration has no JAX analogue — JAX's extension point IS shared buffers
+ donation; see DESIGN.md §2.)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from ..runtime import packed as P


@dataclasses.dataclass
class _DeviceState:
    kind: Optional[str] = None  # JAX platform; None = the default backend
    index: int = 0
    mode: str = "native"       # 'native' | 'transparent'

    @property
    def jax_device(self):
        """The selected device; a kind or index this host does not have
        raises rather than falling back to another device."""
        try:
            devs = jax.devices(self.kind)
        except RuntimeError as e:
            raise ValueError(f"no {self.kind!r} devices on this host: {e}"
                             ) from None
        if not 0 <= self.index < len(devs):
            raise ValueError(f"device index {self.index} out of range: "
                             f"{len(devs)} {devs[0].platform} device(s)")
        return devs[self.index]


class _DeviceAPI:
    """sol.device — the paper's one-call device selection."""

    def __init__(self):
        self.state = _DeviceState()

    def set(self, kind: Optional[str], index: int = 0, *,
            mode: str = "transparent") -> None:
        state = _DeviceState(kind, index, mode)
        state.jax_device                 # validate before switching
        self.state = state

    def stage_params(self, params: Dict[str, Any]) -> Dict[str, Any]:
        dev = self.state.jax_device
        if self.state.mode == "native":
            # native: buffers are already device-resident framework arrays
            return {k: jax.device_put(v, dev) for k, v in params.items()}
        # transparent: pack the many small host tensors into one transfer
        keys = list(params)
        small = [k for k in keys if np.asarray(params[k]).nbytes < 1 << 20]
        big = [k for k in keys if k not in small]
        out: Dict[str, Any] = {}
        if small:
            arrs = P.transfer([np.asarray(params[k]) for k in small], dev)
            out.update(dict(zip(small, arrs)))
        for k in big:
            out[k] = jax.device_put(np.asarray(params[k]), dev)
        return out

    def stage_input(self, x: Any) -> Any:
        if self.state.mode == "transparent":
            return jax.device_put(np.asarray(x), self.state.jax_device)
        return x

    def fetch_output(self, y: Any) -> Any:
        if self.state.mode == "transparent":
            return np.asarray(jax.device_get(y))
        return y


device = _DeviceAPI()
