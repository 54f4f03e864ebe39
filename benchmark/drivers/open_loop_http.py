"""Driver `open_loop_http`: independent users. Arrivals are fixed by the
traffic file and the seed, whatever the server does; a request's latency
counts from the time it was due."""
from benchmark.drivers import _http

warm = _http.warm


def window(run, sut):
    source = _http.source_for(run, sut)
    requests = source.until(
        float(run.traffic.get("ramp_s", 0)) + run.seconds)
    load = _http.Load(run, sut, source,
                      int(run.traffic["client_connections"]),
                      _http.OpenWorker)
    return load.go(run.seconds, requests)
