"""Plain references of the benchmark's model configurations.

One module per ``model`` named in ``bench/configs/<name>.json``.  The
harness (``world.py``, ``reference.py``, ``flops.py``, ``run.py``,
``control.py``, ``compare.py``) knows no model family: everything that
depends on one comes from the configuration's module, which gives

``examples(traffic) -> (train, eval)``
    how many training and held-out examples the traffic asks for;
``make_data(cfg, traffic, seeds) -> (train, test)``
    dicts of aligned host arrays, indexed by example along axis 0 and keyed
    as the program's batch (``seeds``: the streams of ``world.seeds``);
``program_config(cfg)``
    the program's ``repro.configs.ModelConfig`` for this configuration;
``param_shapes(cfg)``
    the program's parameter tree, nested where the program nests, with
    shape tuples for leaves;
``init(cfg, key)``
    the initial weights as that tree, traced (the harness jits it);
``mask_units(cfg) -> {unit type: (rows, n)}``
    the maskable units, one row per layer that a unit type spans;
``loss(params, batch, cfg, masks, precision)``
    mean cross-entropy over one batch dict; ``masks`` ({unit type: (rows,
    n) 0/1}, or None for the whole model) multiply each unit's activation,
    so a masked unit's weights get no gradient, and ``precision`` reaches
    every dot product;
``unit_scores(new, old, cfg) -> {unit type: (rows, n)}``
    Eq. 1: the summed absolute change of every weight that carries the
    unit's axis;
``train_ops(cfg, traffic, alive=None)`` and ``forward_ops(cfg, traffic)``
    operations per example of a training step (forward and backward) and
    of a forward pass of the whole model; ``alive`` is a client's masks,
    None for the whole model; one multiply-add counts as two operations
    (``traffic`` gives the sizes of an example that the configuration
    leaves open, such as a sequence's length);
``kernel_costs(cfg, traffic, masks) -> {kernel: (ops, bytes)}``
    the work that one local step of one client asks of each kernel, for a
    client training under ``masks`` (None: the whole model).

Nothing here imports the program under test, except ``program_config``,
which names the configuration that the program is built from.
"""
import importlib


def load(model: str):
    """The reference module of one architecture, by its config's name."""
    return importlib.import_module(f"bench.models.{model}")
