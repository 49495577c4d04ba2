"""Traffic kind ``serve_open_loop``: Poisson arrivals from ``--seed`` at
the workload file's fixed ``arrivals.rate_per_s``, submitted when due
whatever the system's state — callers that do not wait for each other.
TTFT is counted from the time a request was *due*; the generator's
lateness is reported (``generator_lateness_s`` on the ``window`` line).
The engine, the pool of request shapes and the judging are the closed
loop's; all of it is ``benchmark/serve_driver.py``'s, which documents
the warm-up and the lead-in that end set-up.

Workload file keys: those of ``serve_closed_loop`` — ``clients`` here is
the number of warm-up requests (the last of them the lead-in) — plus
``arrivals`` (``rate_per_s``, ``lead_in_tokens``).
"""
from benchmark import serve_driver

run = serve_driver.run
