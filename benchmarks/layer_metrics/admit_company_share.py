"""Share of the window's admissions (`decode.prefill` spans that carry
`same_bucket_waiting`) that had at least one OTHER waiting prompt of their
own prefill bucket beside them: how often a batched prefill would have
found company."""
from benchmarks.harness import loop_records


def read(rec):
    loop = loop_records.load(rec)
    if loop is None:
        return None
    company = [facts["same_bucket_waiting"]
               for _, _, facts in loop["spans"].get("decode.prefill", ())
               if facts.get("same_bucket_waiting") is not None]
    if not company:
        return None
    return sum(1 for n in company if n >= 1) / len(company)
