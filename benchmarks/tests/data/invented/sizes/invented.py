"""An invented mixture of experts: the test's stand-in for the sizes/
file a new architecture brings."""


def param_bytes(conf, weight_dtype):
    per = {"bf16": 2, "int8": 1}[weight_dtype]
    H, E = conf["hidden_size"], conf["num_experts"]
    return conf["num_hidden_layers"] * E * H * H * per


def flops_per_token(conf):
    H = conf["hidden_size"]
    return {"layers": 2 * conf["num_hidden_layers"] * H * H,
            "head": 2 * H * conf["vocab_size"]}
