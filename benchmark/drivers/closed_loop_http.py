"""Driver `closed_loop_http`: `clients` callers that each wait for their
reply before they send their next request."""
from benchmark.drivers import _http

warm = _http.warm


def window(run, sut):
    source = _http.source_for(run, sut)
    load = _http.Load(run, sut, source, int(run.traffic["clients"]),
                      _http.ClosedWorker)
    return load.go(run.seconds)
