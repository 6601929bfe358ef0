"""DES of the window's last burst, ``launch.serve.decode_request_batch``,
timed alone after the window on the host clock (repeated over at least
0.25 s), per request wire."""


def read(run):
    v = run.host.get("des_s_per_wire")
    return None if v is None else 1e6 * v
