"""Mesh construction. Functions, never module-level constants — importing
this module must not touch jax device state (the dry-run sets
XLA_FLAGS before any jax initialization)."""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType, Mesh

from repro.config import MeshConfig


def _axis_kw(n: int):
    # every axis stays under GSPMD (Auto); explicit sharding is not used
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Production topology: one TPU v5e pod = 16x16 = 256 chips,
    ("data", "model"); multi-pod doubles it with a leading "pod" axis
    (2 x 16 x 16 = 512 chips) over which data parallelism spans DCN/ICI."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))


def make_mesh(cfg: MeshConfig) -> Mesh:
    """Mesh from an explicit MeshConfig (tests / small runs)."""
    return jax.make_mesh(cfg.shape, cfg.axis_names, **_axis_kw(len(cfg.shape)))


def single_device_mesh() -> Mesh:
    return jax.make_mesh((1, 1), ("data", "model"), **_axis_kw(2))


def auto_mesh(model_axis: int = 1) -> Mesh:
    """("data", "model") mesh over every *available* device: data absorbs
    whatever the model axis doesn't. The shape serving/tests want on a CPU
    host forced to N devices (``XLA_FLAGS=--xla_force_host_platform_
    device_count=8`` -> (8//model, model)); on one device it degenerates to
    (1, 1) and drives the identical SPMD code path.
    """
    n = jax.device_count()
    if model_axis < 1 or n % model_axis != 0:
        raise ValueError(f"model_axis {model_axis} must divide device count {n}")
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"),
                         **_axis_kw(2))


def describe_mesh(mesh: Mesh) -> str:
    """One-line topology summary for launcher logs."""
    dims = " x ".join(f"{k}={v}" for k, v in mesh.shape.items())
    return f"{dims} ({len(mesh.devices.flat)} devices, {mesh.devices.flat[0].platform})"
