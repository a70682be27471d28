"""The recurrent state is held in the precision the configuration
states: the server's own count of the bytes its linear-attention layers
hold for their slots is what sizes/<model_type>.py gives
(``recurrent_state_bytes(conf, slots)``, a float32 state and a 16-bit
convolution tail per layer and slot), exactly.

Why bytes and not logits: a state rounded to bfloat16 after every token
moves no served token (reference/compare.py --control state_bf16 puts
the same token first at every position, PERF.md section 2), so no
comparison of what was served can see it, while the served program's
own 16-bit arithmetic moves about one token in twelve. What a narrower
state would buy is its bytes, and those are counted here.
"""

from lib import client, prom
from lib.cell import sizes


def _slots(conf: dict) -> int:
    args = conf["server_args"]
    return int(args[args.index("--batch-slots") + 1])


def before_window(run):
    conf = run.config
    text = client.http(run.url + "/metrics", timeout=30)[1]
    want = sizes(conf["model_type"]).recurrent_state_bytes(
        conf, _slots(conf))
    got = prom.value(text, "kubeinfer_recurrent_state_bytes")
    run.compared["recurrent_state_bytes"] = [got, want]
    if got != want:
        return [f"kubeinfer_recurrent_state_bytes is {got}, "
                f"{conf['name']} holds {want} for its slots: the state "
                "is not kept in the type the configuration states"]
    return []
