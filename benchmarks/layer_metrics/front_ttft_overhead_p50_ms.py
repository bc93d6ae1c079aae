"""What the HTTP front adds to a request's time to the first token, median
over the requests that finished in the window: handler entry to the
engine's queue (`arrival` to `t_submit` of the request record) plus the
engine's first token to its bytes flushed (`http.first_write`)."""
import statistics


def read(rec):
    program = rec.get("program")
    if rec.get("kind") != "serve" or not program:
        return None
    writes = {s[4].get("rid"): s[2] - s[1] for s in program["spans"]
              if s[0] == "http.first_write"}
    costs = [r["t_submit"] - r["arrival"] + writes[r["rid"]]
             for r in program["requests"] if r["rid"] in writes]
    return 1000.0 * statistics.median(costs) if costs else None
