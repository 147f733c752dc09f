"""What ``correct`` has to catch, planted on purpose: never part of a
benchmark run. ``hooks(name)`` gives the hooks that ``run.run_cell`` takes,
so that the fault's numbers pass through the harness's own comparison and
its own result line (``benchmarks/tests/test_correct.py`` at a test's size,
``benchmarks/tools/calibrate.py --plant`` on the chip at the cell's own).

- ``control``: the plain reference at the precision below the one the
  configuration states (its ``control_precision``), in the program's place.
- ``half_batch_reference``: the reference with half of the batch left out,
  in the program's place.
- ``unchanged_state``, ``half_batch``, ``altered_token``: the program's own
  timed path broken underneath.
"""

from __future__ import annotations


def unchanged_state(trainer):
    """A step that returns its state unchanged (metrics still real)."""
    trainer._donate = False
    make = trainer._make_train_step

    def broken():
        real = make()
        return lambda state, batch: (state, real(state, batch)[1])

    trainer._make_train_step = broken


def half_batch(trainer):
    """Half of the batch left out, the mean taken over the rest."""
    import jax
    import jax.numpy as jnp

    make = trainer._make_train_step

    def broken():
        real = make()

        def step(state, batch):
            half = jax.tree.map(
                lambda x: jnp.concatenate([x[: x.shape[0] // 2]] * 2), batch
            )
            return real(state, half)

        return step

    trainer._make_train_step = broken


def altered_token(engine):
    """One token of each request altered where it is produced: the decode
    step's read-back (and fed back, as a wrong sample would be)."""
    real = engine._decode_batch
    vocab = int(engine.model.vocab_size)

    def broken(active):
        real(active)
        for st in active:
            if st.slot >= 0 and len(st.generated) == 3:
                st.generated[-1] = (st.generated[-1] + 1) % vocab
                engine._tok[st.slot] = st.generated[-1]

    engine._decode_batch = broken


PLANTS = {
    "control": {"in_place": "control"},
    "half_batch_reference": {"in_place": "half_batch"},
    "unchanged_state": {"after_build": unchanged_state},
    "half_batch": {"after_build": half_batch},
    "altered_token": {"after_build": altered_token},
}


def hooks(name: str) -> dict:
    return dict(PLANTS[name])
