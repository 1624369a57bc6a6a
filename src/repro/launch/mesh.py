"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* first jax init.
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with `Auto` axes.  The model code places arrays with
    `with_sharding_constraint` and leaves the rest to GSPMD, which only Auto
    axes allow (make_mesh's default is Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = (data, model) = 256 chips.
    Multi-pod: (2, 16, 16) = (pod, data, model) = 512 chips; the `pod` axis
    carries data parallelism across the DCN/ICI-superpod boundary."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices this host actually has, as a 1D data mesh — used by
    the runnable examples on CPU."""
    n = len(jax.devices())
    return auto_mesh((n,), ("data",))


def make_serving_mesh(dp: int = 1, tp: int = 1):
    """The serving engine's (data, model) mesh.  `model` is the tensor axis:
    FC weights split into one FC-PIM bank per shard and the KV cache slices
    one Attn-PIM unit per shard (§5.3); `data` replicates the engine for
    throughput.  Uses the first dp*tp devices."""
    return auto_mesh((dp, tp), ("data", "model"), jax.devices()[: dp * tp])


def parse_mesh(spec: str) -> tuple[int, int]:
    """Parse a ``--mesh dp,tp`` CLI value into (dp, tp)."""
    parts = spec.split(",")
    if len(parts) != 2:
        raise ValueError(f"--mesh wants 'dp,tp', got {spec!r}")
    dp, tp = (int(p) for p in parts)
    if dp < 1 or tp < 1:
        raise ValueError(f"mesh axes must be >= 1, got {spec!r}")
    return dp, tp


def force_host_device_count(n: int) -> None:
    """Ask XLA's CPU backend for `n` host devices.  Only effective BEFORE the
    first jax backend touch (importing jax is fine; creating an array is
    not), so launchers call this right after argument parsing.  A count
    already forced via XLA_FLAGS is left alone."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
