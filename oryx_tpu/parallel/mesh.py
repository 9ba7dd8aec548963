"""Device mesh + compute context: the framework's execution substrate.

TPU-native replacement for the reference's Spark context plumbing
(lambda/AbstractSparkLayer.java:142-173 buildStreamingContext): instead of a
JavaStreamingContext wired to YARN executors, each layer gets a ComputeContext
holding a jax.sharding.Mesh built from config
(``oryx.{batch,speed}.streaming.config``: platform, mesh-shape, mesh-axes).

Conventions:
  * axis "data" shards batches (Spark RDD data-parallel equivalent);
  * axis "model" shards factor/parameter matrices (MLlib block-partitioned
    ALS equivalent); models add more axes as needed via shard_map/pjit;
  * single-device configs get a trivial 1-device mesh so model code is always
    written against a mesh and scales without change.
"""

from __future__ import annotations

import numpy as np


class ComputeContext:
    """Mesh + config handle passed to batch updates and model managers."""

    def __init__(self, config, tier: str = "batch"):
        import jax

        self.config = config
        self.tier = tier
        compute_key = f"oryx.{tier}.streaming.config"
        ccfg = config.get_config(compute_key) if config.has(compute_key) else None
        platform = ccfg.get_string("platform", None) if ccfg else None
        devices = jax.devices(platform) if platform else jax.devices()
        shape = ccfg.get_list("mesh-shape", None) if ccfg else None
        axes = tuple(ccfg.get_list("mesh-axes", ["data", "model"])) if ccfg else ("data", "model")
        if shape is None:
            shape = [len(devices)] + [1] * (len(axes) - 1)
        n_used = int(np.prod(shape))
        if n_used > len(devices):
            raise ValueError(f"mesh shape {shape} needs {n_used} devices, have {len(devices)}")
        dev_array = np.asarray(devices[:n_used]).reshape(shape)
        self.mesh = jax.sharding.Mesh(dev_array, axes)

    @property
    def num_devices(self) -> int:
        return self.mesh.size

    def sharding(self, *spec_axes: "str | None"):
        """NamedSharding over this mesh for the given per-dimension axis names."""
        import jax

        return jax.sharding.NamedSharding(self.mesh, jax.sharding.PartitionSpec(*spec_axes))

    def replicated(self):
        import jax

        return jax.sharding.NamedSharding(self.mesh, jax.sharding.PartitionSpec())


def make_mesh(n_devices: int | None = None, axes: tuple[str, ...] = ("data",), shape=None):
    """Standalone mesh helper for tests/entry points."""
    import jax

    devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    if shape is None:
        shape = (n_devices,) + (1,) * (len(axes) - 1)
    dev_array = np.asarray(devices[: int(np.prod(shape))]).reshape(shape)
    return jax.sharding.Mesh(dev_array, axes)


def row_sharding(mesh, axis: str = "model"):
    """NamedSharding that splits an array's rows (dim 0) over ``axis``."""
    import jax

    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(axis))


def replicated_sharding(mesh):
    """NamedSharding that puts an array whole on every device of ``mesh``."""
    import jax

    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())


def padded_rows(n_rows: int, mesh, axis: str = "model") -> int:
    """``n_rows`` rounded up to a multiple of the shard count of ``axis``."""
    shards = mesh.shape[axis]
    return -(-n_rows // shards) * shards


def put_row_sharded(host: np.ndarray, mesh, axis: str = "model"):
    """Row-split a host array over ``axis`` WITHOUT staging it on any one
    device: each device is handed its own row block straight from ``host``
    (rows past the end, up to the shard count, are zero padding made on the
    host side of the copy). The global array has :func:`padded_rows` rows."""
    import jax

    n = host.shape[0]
    shape = (padded_rows(n, mesh, axis),) + host.shape[1:]

    def block(index):
        rows = index[0]
        lo, hi = rows.start or 0, shape[0] if rows.stop is None else rows.stop
        if hi <= n:
            return host[lo:hi]
        out = np.zeros((hi - lo,) + host.shape[1:], dtype=host.dtype)
        if lo < n:
            out[: n - lo] = host[lo:n]
        return out

    return jax.make_array_from_callback(shape, row_sharding(mesh, axis), block)
