"""Test isolation for the benchmark's CPU rehearsals.

A benchmark run is one process, and between the program's rounds and the
reference it frees the device by deleting every live array
(``perfbench.harness._free_device_state``).  Under pytest one worker process
imports every test module and runs many of them, so that deletion would also
take the module-level arrays of test files it never ran (a ``PARAMS0`` of
``tests/``).  While a module under ``perfbench/`` runs, the deletion spares
the arrays that were alive before the module began."""
import gc
import os
import weakref

import pytest

_PERFBENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "perfbench")


@pytest.fixture(scope="module", autouse=True)
def _spare_other_modules_arrays(request):
    if not str(request.fspath).startswith(_PERFBENCH + os.sep):
        yield
        return
    import jax
    from perfbench import harness
    # weak references: an id alone could be reused by a new array once
    # the one it named is collected
    before = {id(a): weakref.ref(a) for a in jax.live_arrays()}

    def free_own_device_state():
        gc.collect()
        for a in jax.live_arrays():
            ref = before.get(id(a))
            if ref is None or ref() is not a:
                a.delete()

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "_free_device_state", free_own_device_state)
        yield
