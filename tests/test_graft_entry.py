"""Driver entry-point contract tests (__graft_entry__).

``entry()`` is the single-chip compile check; ``dryrun_multichip(n)``
executes the full sharded train step on an n-device mesh in the calling
process — here the conftest's 8 virtual CPU devices.
"""

import pytest

import __graft_entry__ as ge


def test_dryrun_needs_enough_devices():
    import jax

    with pytest.raises(RuntimeError, match="needs"):
        ge.dryrun_multichip(len(jax.devices()) + 1)


@pytest.mark.slow
def test_entry_returns_jittable_fn():
    """The driver's single-chip compile check: entry() then jit-trace."""
    import jax

    fn, args = ge.entry()
    variables, img1, img2 = args
    assert img1.shape == img2.shape == (1, 96, 128, 3)
    # .lower() traces the full flagship forward (what the driver's
    # compile check does before .compile()).
    assert jax.jit(fn).lower(variables, img1, img2) is not None


@pytest.mark.slow
def test_dryrun_multichip_in_process_8_devices(capsys):
    ge.dryrun_multichip(8)
    out = capsys.readouterr().out
    assert "dryrun_multichip ok" in out
    assert "onthefly/shard_map" in out
