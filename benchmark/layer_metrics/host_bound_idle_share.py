"""Share of the traced window in which the chip was idle and the host was
NOT in the round's one fetch: idle seconds of the idlest chip outside
``ds:serve.fetch`` / window (``harness/program_spans.idle_by_span``). Idle
under the fetch is the device finishing and the copy to the host; idle
anywhere else — scheduling, dispatching, committing, the caller between
rounds — is the chip waiting for the host."""
from benchmark.harness import program_spans

HEADER = {"layer": "serve entry / scheduler (inference/serving.py)",
          "unit": "%", "moves": "tpot_p90_ms", "jobs": ["serve"],
          "source": "program_span", "better": "lower"}


def read(run):
    table = program_spans.idle_table(run)
    if table is None:
        return None
    return program_spans.share_outside(table, run["trace"]["window_s"],
                                       "ds:serve.fetch")
