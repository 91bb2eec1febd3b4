"""Faults planted in the program's timed path, for the checks that a
broken run comes out not correct (bench/tests, and bench/calibrate.py's
`--plant` on the chip).

  unchanged   the round step returns its state unchanged
  half_batch  each client's loss is the mean over half of its rows
  token       one token of client 0's first row is altered where the
              loss reads it
  exchange    the client-axis psum of the OTA superposition
              (`Transport.aggregate_mesh`) is left out: each chip decodes
              its own clients alone
"""
import functools


def plant(fault: str) -> None:
    """Break the program's timed path in place (for this process)."""
    import jax
    import jax.numpy as jnp
    from repro.core import pairzero
    from repro.core import transport as tp
    from repro.models import transformer

    if fault == "unchanged":
        real = pairzero.make_zo_step

        @functools.lru_cache(maxsize=None)
        def frozen(*a, **k):
            step = real(*a, **k)
            return lambda params, batch, ctl: (params,
                                               step(params, batch, ctl)[1])
        pairzero.make_zo_step = frozen
    elif fault in ("half_batch", "token"):
        real_loss = transformer.loss_per_client

        def broken(params, cfg, batch, *, impl=None):
            if fault == "half_batch":
                half = batch["tokens"].shape[1] // 2
                batch = {k: v[:, :half] for k, v in batch.items()}
            else:
                tok = batch["tokens"]
                batch = dict(batch, tokens=tok.at[0, 0, -1].set(
                    (tok[0, 0, -1] + 1) % cfg.vocab_size))
            return real_loss(params, cfg, batch, impl=impl)
        transformer.loss_per_client = broken
    elif fault == "exchange":
        # only the superposition's psum goes: the gather of L± that feeds
        # the reported loss and p_k stays whole
        def local_only(self, p_local, ctl, key, axis_names, offset):
            k_total = ctl["mask"].shape[-1]
            full = jnp.zeros(p_local.shape[:-1] + (k_total,), p_local.dtype)
            p = jax.lax.dynamic_update_slice_in_dim(full, p_local, offset,
                                                    axis=-1)
            return self.aggregate(p, ctl, key)
        tp.Transport.aggregate_mesh = local_only
    else:
        raise SystemExit(f"unknown fault {fault!r}")
