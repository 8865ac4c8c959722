"""Multi-chip sharding smoke tests on the virtual 8-device CPU mesh
(provisioned by conftest.py), and __graft_entry__'s jittable entry step."""

import jax
import numpy as np
import pytest


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_dryrun_multichip():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_entry_compiles():
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out[1].shape == (256,)  # the rounds carry: the engine's capacity


def test_entry_step_matches_engine_pass():
    """The entry step, jitted on its own, reproduces the packed buffer
    the engine's pass pulled."""
    import __graft_entry__ as ge

    fn, args, packed = ge.engine_pass()
    got = np.asarray(jax.jit(fn)(*args)[0])
    assert got.shape == packed.shape
    assert (got == packed).all()
    assert packed[1] > 0  # the pass received events
