"""Wave scheduler for mega-cohort cross-device federation.

`waves.py` turns one round's sampled cohort (1k-100k lightweight
clients) into a sequence of static device-sized WAVES, each trained as
ONE compiled XLA program, with per-wave summaries for admission/health
and stacked outputs the streaming spine folds device-side — the bridge
between `parallel/cohort.py` (the compiled engine) and the live round
loop's O(model) aggregation (`core/stream_agg.py`).
"""

from fedml_tpu.device_cohort.waves import (Wave, WaveAdmission,
                                           admission_stats,
                                           make_scaffold_wave_fn,
                                           make_summed_wave_fn,
                                           make_wave_fn, mean_of_sum,
                                           plan_waves)

__all__ = ["Wave", "WaveAdmission", "admission_stats", "make_wave_fn",
           "mean_of_sum",
           "make_summed_wave_fn", "make_scaffold_wave_fn", "plan_waves"]
