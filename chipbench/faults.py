"""Faults planted under the timed path, to show that the check fails.

Each takes the engine before its warm-up and breaks its fused decode
program underneath ``add_request`` and ``step``.  They reach into the
engine's private program on purpose: they exist only to be caught, by
``chipbench/tests`` at a test size and by ``chipbench/readings.py`` at a
cell's own size on the chip.
"""
from __future__ import annotations


def token_altered(eng) -> None:
    """The fused decode returns slot 0's tokens shifted by one: a token
    altered where it is produced, in one slot."""
    import jax.numpy as jnp
    dec, vocab = eng._decode, eng.cfg.vocab

    def broken(h, *args):
        toks, em, *rest = dec(h, *args)
        return (toks.at[0].set(jnp.mod(toks[0] + 1, vocab)), em, *rest)

    eng._decode = broken


def state_unchanged(eng) -> None:
    """The fused decode hands back the paged state it was given: the
    cache writes and exponent bumps of its steps are lost."""
    dec = eng._decode

    def broken(h, params, state, *args):
        toks, em, _, *rest = dec(h, params, state, *args)
        return (toks, em, state, *rest)

    eng._decode = broken


FAULTS = {"token_altered": token_altered, "state_unchanged": state_unchanged}
