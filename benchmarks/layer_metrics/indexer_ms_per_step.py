"""Device ms a decode step in the sparse layers' indexers and selections:
the operations the program scopes `layer<i>/indexer` (the indexer's
queries and weights, the `paged_index_scores` kernel over every cached key
of each row) and `layer<i>/select` (the exact top-k as a list of token
addresses)."""
from program_trace import scoped_ms_per_run


def read(run):
    return scoped_ms_per_run(run, r"/layer\d+/(indexer|select)/",
                             "serve_decode")
