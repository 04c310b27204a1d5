"""The driver's entry points on the actual chip: compile and run the
kernels `__graft_entry__` exposes (the served path end to end is
`chip_smoke.py`'s job)."""


def test_preflight_on_device(capsys):
    """Both selectable Pallas kernels at bench shapes + the fused render
    paths, compiled for the real Mosaic backend, parity-checked."""
    import __graft_entry__ as g
    g.preflight()
    out = capsys.readouterr().out
    assert "preflight OK on tpu" in out
    assert "pallas=real" in out


def test_entry_on_device():
    """The driver's single-chip compile check, on the real chip."""
    import jax

    import __graft_entry__ as g
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    out.block_until_ready()
    assert out.shape == (64, 64, 4)
