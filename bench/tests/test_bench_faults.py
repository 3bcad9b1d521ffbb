"""With the timed path broken underneath, a run's `correct` comes out
false: once for each fault a cell can have.

  frozen      every tick returns its state unchanged;
  half        half of the fleet's members are left out (their state is
              put back after each dispatch), the rest run;
  altered     an answer is altered where it is produced: every value
              the state machine applies is off by one (fleet cells), or
              every get returns its value plus one (the KV cell).

No cell runs on more than one chip, so there is no exchange between
chips to leave out.  The KV cell serves one request at a time, so it has
no batch to halve.  Each fault is planted in the program (`src/`) by the
test alone; the reference imports nothing of it.
"""
import jax
import jax.numpy as jnp
import pytest

from benchtest import CPU


@pytest.fixture
def fresh_programs():
    """Forget compiled epoch programs, so a planted fault is traced."""
    from repro.core import fleet, runtime
    for cache in (fleet._FLEET_EPOCH_CACHE, runtime._EPOCH_CACHE):
        cache.clear()
    yield
    for cache in (fleet._FLEET_EPOCH_CACHE, runtime._EPOCH_CACHE):
        cache.clear()


def frozen(monkeypatch):
    from repro.core import step
    real = step.tick

    def tick(state, *args, **kw):
        _, metrics = real(state, *args, **kw)
        return state, metrics
    monkeypatch.setattr(step, "tick", tick)


def altered_apply(monkeypatch):
    from repro.core import step
    real = step.apply_step

    def apply_step(state, *args, **kw):
        out = real(state, *args, **kw)
        return dict(out, kv=jnp.where(out["kv"] != state["kv"],
                                      out["kv"] + 1, out["kv"]))
    monkeypatch.setattr(step, "apply_step", apply_step)


def half(monkeypatch):
    from repro.core.fleet import FleetSim

    def leave_out(method):
        def wrapped(self, *args, **kw):
            before = self._state
            out = method(self, *args, **kw)
            B = self.shapes.B
            self._state = jax.tree.map(
                lambda new, old: new.at[B // 2:].set(old[B // 2:]),
                self._state, before)
            return out
        return wrapped
    # the donated input buffers must survive the call to be put back
    for name in ("run_epoch", "_run_scan"):
        real = getattr(FleetSim, name)

        def keep(self, *args, _real=real, **kw):
            self._state = jax.tree.map(jnp.copy, self._state)
            return _real(self, *args, **kw)
        monkeypatch.setattr(FleetSim, name, leave_out(keep))


def altered_get(monkeypatch):
    from repro.kvstore.service import BWKVService
    real = BWKVService.get

    def get(self, key, **kw):
        value, fence = real(self, key, **kw)
        return value + 1, fence
    monkeypatch.setattr(BWKVService, "get", get)


FLEET = ["paper4r.managed", "paper4r.scan"]
CASES = [(c, f) for c in FLEET for f in (frozen, half, altered_apply)] + \
    [("digest350.kv-ycsbA", f) for f in (frozen, altered_get)]


@pytest.mark.parametrize("name,fault", CASES,
                         ids=[f"{c}-{f.__name__}" for c, f in CASES])
def test_fault_is_caught(tiny, runner, monkeypatch, fresh_programs, name,
                         fault):
    fault(monkeypatch)
    out = runner.run_cell(tiny, name, 4242, 1.0, False, device=CPU)
    assert out["correct"] is False, out["checks"]
