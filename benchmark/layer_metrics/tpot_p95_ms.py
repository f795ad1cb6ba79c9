"""95th percentile over the requests due in the window: per request, (last feed - first feed)
over the tokens after the first feed.
The driver takes it from the whole window with the host's clock; it stands
among the per-layer metrics because the serving loop's lock makes it swing
by half from run to run (PERF.md)."""


def read(facts, suffix):
    return facts["e2e"].get("tpot_p95_ms")
