"""Device places (reference: paddle/fluid/platform/place.h:26-52).

On TPU the set of devices is owned by JAX/PJRT; Place objects survive as
thin user-facing handles so `Executor(fluid.TPUPlace(0))` reads like the
reference's `Executor(fluid.CUDAPlace(0))`. A place names its backend:
`TPUPlace` resolves on the "tpu" backend and raises where there is none —
it never stands for "whatever the default backend is". Code that wants
"the chip if present, else the host" says so with `default_place()`.
"""

from __future__ import annotations

import jax


class Place:
    device_id: int = 0

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == getattr(other, "device_id", 0)

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    backend: str

    def jax_device(self):
        # local_devices: under multi-host (jax.distributed) a process may
        # only place computations on its own devices; jax.devices()[0] would
        # be process 0's device everywhere. Raises RuntimeError when the
        # process has no such backend.
        return jax.local_devices(backend=self.backend)[self.device_id]


class CPUPlace(Place):
    backend = "cpu"

    def __repr__(self):
        return "CPUPlace"


class TPUPlace(Place):
    backend = "tpu"

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def __repr__(self):
        return f"TPUPlace({self.device_id})"


# Reference-compat alias: scripts written against fluid.CUDAPlace(0) run on
# the accelerator (TPU) unchanged.
CUDAPlace = TPUPlace
XPUPlace = TPUPlace


class TPUPinnedPlace(Place):
    backend = "cpu"

    def __repr__(self):
        return "TPUPinnedPlace"


CUDAPinnedPlace = TPUPinnedPlace


def is_compiled_with_tpu() -> bool:
    return jax.default_backend() == "tpu"


def is_compiled_with_cuda() -> bool:
    # Reference-compat shim: "is there an accelerator".
    return is_compiled_with_tpu()


def default_place() -> Place:
    return TPUPlace(0) if is_compiled_with_tpu() else CPUPlace()
