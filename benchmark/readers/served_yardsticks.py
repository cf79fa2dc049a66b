"""Shares of a peak for a served model of any family. What the work
needs comes from the family's count module, found by the configuration's
``family`` as ``benchmark/counts/<family>.py`` (a configuration without
the key is ProGen's, as ``models.build_model`` reads it), through three
functions of (config, counters):

  ``window_flops``  FLOPs the window's prefilled and decoded tokens need
  ``decode_need``   (FLOPs, bytes) one decode step needs
  ``prefill_need``  (FLOPs, bytes) one execution of the chunk program
                    needs

each ``None`` where the driver's counters do not hold what it counts
from. The spec's ``what`` picks:

  ``mfu``      ``window_flops`` over window x chips x the bf16 peak
  ``decode``   least time for ``decode_need`` (bytes over the HBM peak,
               FLOPs over the bf16 peak, whichever is longer) over the
               median device time of the program ``match`` names
  ``prefill``  the same for ``prefill_need``

A family without a count module, a program without the counters, a run
without a trace or a device without published peaks gives nothing to
read. ``run.notes["roofline.<what>"]`` keeps the bytes, the FLOPs, the
least and the device seconds and which peak binds.
"""

import importlib

from benchmark import flops
from benchmark.readers import trace_program


def counts_for(config: dict):
    """The count module of the configuration's family, or None."""
    name = f"benchmark.counts.{config.get('family', 'progen')}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise  # the module is there and imports something that is not
        return None


def read(run, spec):
    what = spec["what"]
    if what not in ("mfu", "decode", "prefill"):
        raise ValueError(f"served_yardsticks: unknown {what!r}")
    counts = counts_for(run.config)
    if run.peak is None or counts is None:
        return None
    k = run.counters
    if what == "mfu":
        need = counts.window_flops(run.config, k)
        if need is None:
            return None
        return 100.0 * need / (k["window_s"] * k["chips"] * run.peak["bf16_flops"])
    need = getattr(counts, f"{what}_need")(run.config, k)
    took_ms = trace_program.read(run, {"match": spec["match"]})
    if need is None or took_ms is None:
        return None
    took = took_ms / 1000.0
    least, bound = flops.roofline_seconds(*need, run.peak)
    run.notes[f"roofline.{what}"] = {
        "bound": bound, "least_s": least, "device_s": took,
        "bytes": need[1], "flops": need[0],
    }
    return 100.0 * least / took
