"""Fixtures shared by the serving tests."""
import threading

import pytest


@pytest.fixture
def held(monkeypatch):
    """Keeps every search a ServingFrontend launches unfinished, as far as
    the frontend can tell, until the test releases it: the frontend's
    waiter blocks in `PendingSearch.wait`, which first takes one permit of
    the semaphore this fixture yields."""
    from repro.serve.engine import PendingSearch
    sem = threading.Semaphore(0)
    real = PendingSearch.wait

    def wait(self):
        assert sem.acquire(timeout=60), "a launched search was never released"
        real(self)

    monkeypatch.setattr(PendingSearch, "wait", wait)
    yield sem
    sem.release(1000)
