"""Ex10 — long-context sequence parallelism: ring attention and Ulysses
over a device mesh.

No reference analog (PaRSEC predates ring attention, SURVEY §5.7) — this
is the framework's first-class long-context support: one logical
sequence is sharded across a chip ring; ring attention rotates K/V
blocks with ``ppermute`` while accumulating an online softmax, Ulysses
reshards seq→head with ``all_to_all`` and runs dense attention.  On
hardware the rotations ride ICI; under this example they run on the
virtual CPU mesh (8 devices) and must match a single-device oracle.
"""

import os as _os, sys as _sys
_sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), ".."))  # run without install

# the virtual mesh must be configured before jax initializes: force the
# CPU platform (one chip cannot host an 8-way ring)
_os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in _os.environ.get("XLA_FLAGS", ""):
    _os.environ["XLA_FLAGS"] = _os.environ.get("XLA_FLAGS", "") + \
        " --xla_force_host_platform_device_count=8"

import numpy as np


def main() -> None:
    import jax
    import jax.numpy as jnp

    from parsec_tpu.parallel import (
        attention_reference,
        make_mesh,
        ring_attention,
        ulysses_attention,
    )

    devs = jax.devices()
    mesh = make_mesh((len(devs), 1), axes=("sp", "unused"), devices=devs)
    B, S, H, D = 2, 16 * len(devs), 8, 32
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    q, k, v = mk(), mk(), mk()

    ref = attention_reference(q, k, v, causal=True)
    ring = ring_attention(q, k, v, mesh, axis="sp", causal=True)
    uly = ulysses_attention(q, k, v, mesh, axis="sp", causal=True)

    err_r = float(jnp.max(jnp.abs(ring - ref)))
    err_u = float(jnp.max(jnp.abs(uly - ref)))
    assert err_r < 1e-4 and err_u < 1e-4, (err_r, err_u)
    print(f"ex10 sequence-parallel: seq {S} over {len(devs)}-device ring, "
          f"ring err {err_r:.1e}, ulysses err {err_u:.1e}: OK")


if __name__ == "__main__":
    main()
