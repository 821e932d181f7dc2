"""One general driver per kind of traffic; a traffic file names its kind,
and ``chipbench/drivers/<kind>.py`` holds its driver.

Each driver takes the cell and the seed, and offers:

* ``setup()``: build the inputs from the seed, the session and whatever
  serves it, and warm every shape the window will use;
* ``window(seconds)``: the measured work, returning the end-to-end numbers
  it knows by name;
* ``traced()``: the shorter window that a ``--trace 1`` run profiles,
  returning the work counts its per-layer readers need;
* ``numbers()``: after the window, the numbers that decide ``correct``;
* ``close()``: stop what it started.
"""
from __future__ import annotations

#: the kernel backend every driver pins, with no fallback: the compiled
#: Pallas kernel, as a TPU deployment runs it
BACKEND = "pallas"


def driver_for(kind: str):
    """The driver class of a traffic kind: ``Driver`` in
    ``chipbench/drivers/<kind>.py``, so a new kind is a new file."""
    import importlib

    try:
        mod = importlib.import_module(f"{__name__}.{kind}")
    except ModuleNotFoundError as e:
        raise KeyError(f"no driver for traffic kind {kind!r}") from e
    return mod.Driver


def program_layers(net) -> list:
    """The program's CNN as the configuration file writes its layers."""
    return [{"kind": l.kind, "in_ch": l.in_ch, "out_ch": l.out_ch,
             "kh": l.kh, "kw": l.kw, "stride": l.stride, "ih": l.ih,
             "iw": l.iw, "residual": l.residual, "padding": l.padding}
            for l in net]


def program_board(dev) -> dict:
    return {"pes": dev.pes, "on_chip_bytes": dev.on_chip_bytes,
            "off_chip_gbps": dev.off_chip_gbps, "clock_hz": dev.clock_hz,
            "wordbytes": dev.wordbytes}


def resolve(config: dict):
    """The program's CNNs and boards for the configuration's names, after
    checking that they are the ones the configuration states: the
    reference evaluates the file's layers, so the program must run the
    same."""
    from repro.cnn.registry import get_cnn
    from repro.fpga.boards import get_board

    nets = {name: get_cnn(name) for name in config["nets"]}
    boards = {name: get_board(name) for name in config["boards"]}
    for name, net in nets.items():
        if program_layers(net) != config["nets"][name]:
            raise RuntimeError(f"the program's {name} differs from the "
                               f"configuration's layers")
    for name, dev in boards.items():
        if program_board(dev) != config["boards"][name]:
            raise RuntimeError(f"the program's board {name} differs from "
                               f"the configuration's")
    return nets, boards


def live_ces(seg_end, seg_pipe, seg_nce, n_layers: int) -> int:
    """CEs that hold at least one layer, summed over a batch of designs
    (a pipelined block wider than its segment leaves the rest idle)."""
    import numpy as np

    prev = np.concatenate([np.zeros((len(seg_end), 1), seg_end.dtype),
                           seg_end[:, :-1]], axis=1)
    length = np.clip(np.minimum(seg_end, n_layers) - prev, 0, None)
    ces = np.where(seg_pipe, seg_nce, 1)
    return int(np.where(length > 0, np.minimum(ces, length), 0).sum())
