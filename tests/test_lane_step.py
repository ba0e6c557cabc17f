"""The lane engine's advance step on its own: what it leaves alone.

``batch_jax._advance_step`` moves every lane one schedule step toward its
event target.  A finished lane, and a lane already at its target (as the
padding lanes of a chunk are), must come back bit for bit as they went
in, whatever their phase, and the step must hand back the state it was
given: the same entries, shapes and dtypes, so the lane loop can carry it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batch_jax import _advance_step
from repro.core.simulator import (_CKPT, _DOWN, _PROCKPT, _RECOVER, _VERIFY,
                                  _WORK)

PHASES = {"WORK": _WORK, "CKPT": _CKPT, "PROCKPT": _PROCKPT, "DOWN": _DOWN,
          "RECOVER": _RECOVER, "VERIFY": _VERIFY}
STEP_KW = dict(c=60.0, cp=30.0, d=10.0, r=30.0, time_base=120000.0)
NOW, TARGET = 1000.0, 1500.0


def _lanes(phase):
    """Three lanes in ``phase``: mid-phase short of its target, the same
    lane finished, and a lane whose target is its clock (padding)."""
    in_phase = phase != _WORK
    f8 = {
        "now": [NOW, NOW, TARGET], "target": [TARGET, TARGET, TARGET],
        # A phase under way ends after the target; work has no end.
        "phase_end": [NOW + 800.0 if in_phase else np.inf] * 3,
        "done": [700.0] * 3, "saved": [400.0] * 3,
        "saved_clean": [400.0] * 3, "period_start": [640.0] * 3,
        "period": [1200.0] * 3, "wpp": [1140.0] * 3, "w_rem": [900.0] * 3,
        "win_end": [-np.inf] * 3, "win_rem": [np.inf] * 3,
        "v_wp": [600.0] * 3, "v_rem": [600.0] * 3,
        "time_ckpt": [60.0] * 3, "time_prockpt": [30.0] * 3,
        "time_down": [40.0] * 3, "time_downtime": [10.0] * 3,
        "time_recovery": [30.0] * 3, "time_lost": [50.0] * 3,
        "time_verify": [20.0] * 3,
    }
    i4 = {"phase": [phase] * 3, "n_periodic_ckpts": [1] * 3,
          "n_prockpts": [1] * 3, "n_rollbacks": [1] * 3,
          "n_verifications": [2] * 3, "n_deep_rollbacks": [0] * 3,
          "n_dirty": [0] * 3, "pc": [0] * 3}
    flags = {"finished": [False, True, False], "corrupted": [False] * 3,
             "verify_then_ckpt": [False] * 3, "last_period": [False] * 3}
    s = {k: jnp.asarray(v, jnp.float64) for k, v in f8.items()}
    s.update({k: jnp.asarray(v, jnp.int32) for k, v in i4.items()})
    s.update({k: jnp.asarray(v, bool) for k, v in flags.items()})
    kc = {"wwp": jnp.full(3, np.inf), "vcost": jnp.full(3, 20.0),
          "nv": jnp.full(3, 2, jnp.int32), "keep": jnp.ones(3, jnp.int32)}
    return s, kc


@pytest.mark.parametrize("name", list(PHASES))
def test_finished_and_padding_lanes_are_inert(name):
    with jax.enable_x64(True):
        s, kc = _lanes(PHASES[name])
        out = _advance_step(s, kc, **STEP_KW)
        assert out.keys() == s.keys()
        for k, v in s.items():
            assert (out[k].shape, out[k].dtype) == (v.shape, v.dtype), k
            got, was = np.asarray(out[k]), np.asarray(v)
            assert got[1:].tobytes() == was[1:].tobytes(), k
        # The mid-phase lane moved: to its target, still in its phase.
        assert float(out["now"][0]) == TARGET
        assert int(out["phase"][0]) == PHASES[name]
