"""Kernel-backend registry: the single place where "which implementation
runs the SAM hot path" is decided.

Three backends ship with the repo (see docs/kernels.md):

  * ``"ref"``              — the pure-jnp oracles in `kernels/ref.py`. Always
                             available, fully differentiable through XLA,
                             O(N·W) per step. The correctness baseline.
  * ``"pallas"``           — the compiled Pallas TPU kernels. The production
                             path on TPU hardware, and the default there.
  * ``"pallas-interpret"`` — the same Pallas kernels run through the Pallas
                             interpreter. Slow, but runs anywhere and is
                             bit-accurate to the kernel logic — used by the
                             parity tests on CPU.

Resolution order for ``resolve(spec)``:

  1. an explicit ``KernelBackend`` instance is used as-is;
  2. an explicit name (e.g. from ``MemoryConfig.backend``) is looked up;
  3. ``None`` falls back to the ``REPRO_KERNEL_BACKEND`` environment
     variable, and finally to the platform default: ``"pallas"`` when
     JAX's default backend is a TPU, ``"ref"`` everywhere else. On a TPU
     the served path therefore runs the compiled kernels unless a caller
     names another backend; ``"pallas-interpret"`` is only ever chosen by
     name (the CPU tests).

The backend name is trace-time static: it selects which primitives get
staged into the jitted computation, it is not a runtime switch.

Adding a backend
----------------
Register a new :class:`KernelBackend` under a fresh name. A backend is a
set of flags (``use_pallas``/``interpret``) plus an optional ``overrides``
table mapping op names (``"topk_read"``, ``"fused_read"``,
``"scatter_rows"``, ``"lsh_hash"``, ``"lra_topn"``, ``"usage_argmin"``,
``"sparse_write_update"``) to callables
with the override signatures listed in docs/kernels.md (the ref signatures
plus the trailing keyword config each op forwards, e.g. ``topk_read``
receives ``block_n=``). `kernels/ops.py` consults
``overrides`` first, then the flags, then falls back to the oracle — so a
partial backend (say, only a faster scatter) is valid.

    from repro.kernels import registry
    registry.register(registry.KernelBackend(
        name="mybackend", overrides={"scatter_rows": my_scatter}))
    cfg = MemoryConfig(backend="mybackend")
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Mapping, Optional, Union

import jax

ENV_VAR = "REPRO_KERNEL_BACKEND"


@dataclasses.dataclass(frozen=True)
class KernelBackend:
    """A named kernel implementation set.

    ``use_pallas``/``interpret`` drive the built-in dispatch in
    `kernels/ops.py`; ``overrides`` lets a backend swap in its own callable
    per op without touching the dispatch layer.
    """

    name: str
    use_pallas: bool = False
    interpret: bool = False
    overrides: Mapping[str, Callable] = dataclasses.field(default_factory=dict)

    def impl(self, op: str) -> Optional[Callable]:
        """Return this backend's override for ``op``, or None."""
        return self.overrides.get(op)


_REGISTRY: dict[str, KernelBackend] = {}


def register(backend: KernelBackend, *, allow_replace: bool = False) -> KernelBackend:
    """Register ``backend`` under its name. Replacing a built-in requires
    ``allow_replace=True`` (used by tests; production code should pick a new
    name)."""
    if backend.name in _REGISTRY and not allow_replace:
        raise ValueError(f"backend {backend.name!r} already registered")
    _REGISTRY[backend.name] = backend
    return backend


def unregister(name: str) -> None:
    if name in ("ref", "pallas", "pallas-interpret"):
        raise ValueError(f"cannot unregister built-in backend {name!r}")
    _REGISTRY.pop(name, None)


def available() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get(name: str) -> KernelBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown kernel backend {name!r}; available: {available()}"
        ) from None


BackendSpec = Union[None, str, KernelBackend]


def platform_default() -> str:
    """The backend an unnamed spec resolves to on this platform."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def resolve(spec: BackendSpec = None) -> KernelBackend:
    """Resolve a backend spec (instance | name | None) to a KernelBackend."""
    if isinstance(spec, KernelBackend):
        return spec
    if spec is None:
        spec = os.environ.get(ENV_VAR) or platform_default()
    return get(spec)


register(KernelBackend(name="ref"))
register(KernelBackend(name="pallas", use_pallas=True, interpret=False))
register(KernelBackend(name="pallas-interpret", use_pallas=True, interpret=True))
