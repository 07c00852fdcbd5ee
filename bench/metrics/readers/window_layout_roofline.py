"""`paged_kinds_roofline` / `flash_kinds_roofline` (`params.reader`) over a
configuration that names its window layers by a 0/1 layout and gives every
layer the same query heads (SmallThinker: `sliding_window_layout`,
`sliding_window_size`, `num_attention_heads`): the three keys those readers
read (`layer_types`, `num_attention_heads_per_layer`, `sliding_window`) are
made from the configuration's own (`params.layout_key`, `heads_key`,
`window_key`) and the accepted reader does the rest, with the accepted
yardstick (bench/roofline_window.py). The shapes priced go to the run's log."""

import types

from benchlib.files import load_module


def read(m, params, ctx):
    which = "rehearse_sizes" if ctx.rehearse else "sizes"
    sizes = ctx.config[which]
    layout = list(sizes[params["layout_key"]])
    kinds = ["sliding_attention" if banded else "full_attention" for banded in layout]
    named = {**sizes, "layer_types": kinds, "sliding_window": sizes[params["window_key"]],
             "num_attention_heads_per_layer": [sizes[params["heads_key"]]] * len(layout)}
    inner = types.SimpleNamespace(**{**vars(ctx), "config": {**ctx.config, which: named}})
    value = load_module(f"metrics/readers/{params['reader']}.py").read(m, params, inner)
    if value is not None:
        ctx.log(f"{params['reader']} over {params['layout_key']} {layout}: {kinds.count('full_attention')} full and "
                f"{kinds.count('sliding_attention')} window layers (band {named['sliding_window']}), "
                f"{sizes[params['heads_key']]} query heads over {sizes['num_key_value_heads']} K/V heads of "
                f"{sizes['head_dim']}")
    return value
