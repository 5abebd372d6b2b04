"""Plain float32 `jax.numpy` forward passes, independent of the program:
no kernels, no fusion tricks, matmul precision `highest`. They read the
same parameter arrays as the system and decide `correct`."""


def compare(got, want, tolerance, sample):
    """The verdict of one comparison: the largest |got - want| over the
    largest |want| against `tolerance`, as the dict a run logs."""
    import jax.numpy as jnp
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    return {"ok": bool(err <= tolerance), "relative_error": err,
            "tolerance": tolerance, "sample": sample}
