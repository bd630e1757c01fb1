"""Worker runtime: seconds the program spent taking compiled programs
out of the persistent cache before the timed window opened (read,
decompress, deserialize, load onto the device) -- the sum of JAX's
``cache_retrieval_time_sec``, which fires for hits only."""

from benchmark import setup_spans


def read(facts):
    return setup_spans.cache_load_s(facts)
