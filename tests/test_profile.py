"""The drivers' ``--profile DIR``: the timed steps traced with the JAX
profiler, each step a ``StepTraceAnnotation`` an operator can find."""

import glob

import pytest
from jax.profiler import ProfileData

from repro.launch import serve, train

TINY = ["--arch", "gemma-2b", "--reduced", "--batch", "2"]
DRIVERS = [
    (serve, ["--prompt-len", "16", "--gen", "3"], {"prefill": 1, "decode": 3}),
    (train, ["--seq", "32", "--steps", "3", "--log-every", "0"], {"train": 3}),
]


@pytest.mark.parametrize("driver,args,steps", DRIVERS,
                         ids=["serve", "train"])
def test_profile_writes_a_trace_of_the_steps(driver, args, steps, tmp_path):
    driver.main(TINY + args + ["--profile", str(tmp_path)])
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in steps and "step_num" in dict(e.stats):
                    seen.setdefault(e.name, set()).add(
                        dict(e.stats)["step_num"])
    assert {k: len(v) for k, v in seen.items()} == steps
