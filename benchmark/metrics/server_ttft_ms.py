"""Time to first token as the server sees it: median over the requests of
the traced slice, from the start of the request's first act on its handler
thread (``bigdl:generate_admit``) to the end of its
``bigdl:generate_first_token_wait``, ms. The enclosing
``bigdl:generate_request`` begins microseconds earlier but lasts the whole
answer, and the profiler keeps only spans that ended inside the slice."""
from benchmark.lib import spans
from benchmark.lib.traffic import percentile


def read(run):
    return percentile(spans.since_ms(run.get("planes"), "generate_admit",
                                     "generate_first_token_wait"), 50)
