"""The server serves this configuration's model and no other: its own
count of parameter bytes is what sizes/<model_type>.py gives for the
configuration, and its tensor-parallel degree the configuration's."""

from lib import client, prom
from lib.cell import sizes


def before_window(run):
    conf, wrong = run.config, []
    text = client.http(run.url + "/metrics", timeout=30)[1]
    want = sizes(conf["model_type"]).param_bytes(conf, conf["weight_dtype"])
    got = prom.value(text, "kubeinfer_model_param_bytes")
    run.compared["param_bytes"] = [got, want]
    if got != want:
        wrong.append(f"kubeinfer_model_param_bytes is {got}, "
                     f"{conf['name']} is {want}: another model is served")
    if prom.value(text, "kubeinfer_engine_tp_degree") != conf["tp"]:
        wrong.append("kubeinfer_engine_tp_degree is not the "
                     "configuration's")
    return wrong
