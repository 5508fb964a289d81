"""Share of the window's served requests that found the decode pool full
and waited for a seat in it: FlightRecords with a ``pool_seat_wait_s`` (the
span ``gofr.pool.seat_wait``; a request seated at once carries None). A
program whose records have no such field (any commit before PR 37, where a
full pool refused the request and it decoded solo) reads nothing."""


def read(run):
    done = [r for r in run.flights if r.get("status") == "ok"]
    if not any("pool_seat_wait_s" in r for r in done):
        return None
    return 100.0 * sum(1 for r in done if r["pool_seat_wait_s"] is not None) / len(done)
