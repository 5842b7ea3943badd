"""The keys the live rows of a decode step SELECTED over the keys they could
see, from the engine's own `serve.selected_keys` and `serve.visible_keys`
observations (one each a decode step, over the whole run): how sparse the
traffic makes the attention. 100% where every context is within
`index_topk`."""


def read(ctx):
    obs = ctx.counters["observations"]
    picked, seen = obs.get("serve.selected_keys"), obs.get(
        "serve.visible_keys")
    if not picked or not seen or not seen["mean"]:
        return None
    return 100.0 * picked["mean"] / seen["mean"]
