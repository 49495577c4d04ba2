"""Traffic kind ``serve_family_loop``: ``serve_closed_loop``'s closed
loop — ``clients`` callers that each wait for their reply, one shared
pool of request shapes ordered by ``--seed``, a staggered first
generation that ends set-up — over a model *family* named by the
configuration file's ``model_type``. Everything is
``benchmark/serve_driver.py``'s; ``benchmark/FAMILIES.md`` says what a
family brings.

Workload file keys: those of ``serve_closed_loop`` (``engine``,
``clients``, ``prompt`` / ``output``, ``pool_requests``, ``sampled`` —
``top_k`` may be absent —, ``first_generation_max_new``, ``trace``,
``check_requests``, ``limits``, ``source``) and what the family reads
(``phi4flash``: ``slot_positions``).
"""
from benchmark import serve_driver

run = serve_driver.run
