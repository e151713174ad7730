"""The chip benchmark's correctness comparison at smoke sizes on the CPU:
the system's dynamic and reconfigured rounds, read as the benchmark reads
them, agree with the plain reference (``benchmarks/chip/reference``), and
the control, the reference computed in bfloat16 in the system's place,
fails the cell's limits, as does the local step computed in bfloat16 over
float32 parameters where the cell compares ``loss_mean_gap``."""
import jax
import jax.numpy as jnp
import pytest

from bench_smoke import smoke_cell

from benchmarks.chip import check, generator, session

SEED = 2**31 + 77


def _readings(cell):
    pool = generator.ImagePool(SEED, cell["traffic"], cell["config"]["arch"],
                               cell["config"]["per_worker_batch"])
    sess = session.Session(cell, jax.devices(), SEED)
    it = sess.feed(pool)
    rec = sess.warm_up(it)
    pool.stop = True
    for _ in it:
        pass
    sess.free()
    ref = check.reference_readings(cell, pool, session.seed_key(SEED))
    return pool, rec, ref


@pytest.mark.parametrize("name,registry", [
    ("resnet18.dynamic", "resnet18"),
    ("resnet18.reconfigured", "resnet18"),
    ("wideresnet50-2.dynamic", "wideresnet50-2"),
    ("resnet18.reconfigured", "wideresnet50-2"),
])
def test_system_matches_reference_and_control_fails(name, registry,
                                                    monkeypatch):
    cell, _ = smoke_cell(name, monkeypatch, registry=registry)
    pool, rec, ref = _readings(cell)
    numbers = check.compare(rec, ref)
    # the CPU runs both sides in float32: agreement to rounding, which the
    # duals v (differences of nearly equal consensus values) amplify most
    assert max(numbers.values()) < 1e-3, numbers
    assert check.verdict(numbers, cell["limits"]), numbers
    key = session.seed_key(SEED)
    control = check.reference_readings(cell, pool, key, dtype=jnp.bfloat16)
    bad = check.compare(control, ref)
    assert not check.verdict(bad, cell["limits"]), bad
    if "loss_mean_gap" in cell["limits"]:
        low = check.reference_readings(cell, pool, key,
                                       compute_dtype=jnp.bfloat16)
        bad = check.compare(low, ref)
        assert not check.verdict(bad, cell["limits"]), bad
