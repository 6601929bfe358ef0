"""SER of the window's last burst of responses,
``launch.serve.encode_response_batch``, timed alone after the window on the
host clock (repeated over at least 0.25 s), per response wire."""


def read(run):
    v = run.host.get("ser_s_per_wire")
    return None if v is None else 1e6 * v
