"""Faults a served cell can have, planted underneath the benchmark's stamps.

Each is a ``patch`` for ``harness.Session``: it replaces the engine's
``step_slots`` before the harness wraps it, so the window's own call
produces the fault.  ``bench/tests/test_output_check.py`` sees each fail
the output check at a test size; ``bench/calibrate.py --faults`` reads
them at a cell's own size on the chip.
"""
import numpy as np


def stale_state(sess):
    """Each decode step returns the cache it was given."""
    eng, step = sess.eng, sess.eng.step_slots

    def f(gen, tok, pos, active, key, level=None):
        cache = eng.cache
        out = step(gen, tok, pos, active, key, level)
        eng.cache = cache
        return out
    eng.step_slots = f


def half_batch(sess):
    """Each decode step computes the first half of the slots only; the
    rest emit the pad token."""
    eng, step = sess.eng, sess.eng.step_slots

    def f(gen, tok, pos, active, key, level=None):
        act = np.array(active, bool)
        act[eng.batch // 2:] = False
        return step(gen, tok, pos, act, key, level)
    eng.step_slots = f


def altered_token(sess):
    """The decode step's token of slot 0 is off by one where produced."""
    eng, step = sess.eng, sess.eng.step_slots
    vocab = sess.config["vocab"]

    def f(gen, tok, pos, active, key, level=None):
        toks, key = step(gen, tok, pos, active, key, level)
        toks = np.array(toks)
        toks[0] = (toks[0] + 1) % vocab
        return toks, key
    eng.step_slots = f


ALL = {f.__name__: f for f in (stale_state, half_batch, altered_token)}
