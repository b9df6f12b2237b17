"""Every example must run end-to-end in --smoke mode (the reference ships
runnable examples under pyzoo/zoo/examples; these are the CI-checked
equivalents)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the two heaviest smokes (~90s combined) run in the slow tier; their
# subject matter keeps tier-1 coverage through test_objectdetection.py /
# test_int8_dataflow.py
_SLOW = {
    "examples/imageclassification/int8_dataflow_train.py",
    "examples/objectdetection/ssd_example.py",
    # heaviest smokes re-tiered for the tier-1 870s budget
    "examples/textgeneration/lm_generate_example.py",
    "examples/textclassification/bert_classifier_example.py",
    "examples/imageclassification/pretrained_import.py",
    "examples/imageclassification/resnet_transfer.py",
    "examples/parallel/moe_pipeline_example.py",
    "examples/seq2seq/chatbot_example.py",
    "examples/inference/quantized_inference_example.py",
}

EXAMPLES = [
    "examples/recommendation/ncf_example.py",
    "examples/recommendation/wide_and_deep_example.py",
    "examples/imageclassification/resnet_transfer.py",
    "examples/imageclassification/pretrained_import.py",
    "examples/imageclassification/int8_dataflow_train.py",
    "examples/textclassification/bert_classifier_example.py",
    "examples/tfrecord/tfrecord_train.py",
    "examples/serving/serving_example.py",
    "examples/zouwu/forecast_example.py",
    "examples/cluster/pod_train.py",
    "examples/parallel/moe_pipeline_example.py",
    "examples/objectdetection/ssd_example.py",
    "examples/anomalydetection/anomaly_example.py",
    "examples/seq2seq/chatbot_example.py",
    "examples/automl/autots_example.py",
    "examples/nnframes/nn_classifier_example.py",
    "examples/gan/gan_example.py",
    "examples/inference/quantized_inference_example.py",
    "examples/xshard/xshard_example.py",
    "examples/longcontext/long_context_example.py",
    "examples/textgeneration/lm_generate_example.py",
]


# examples whose --smoke path needs an optional extra (pyproject extras)
_NEEDS = {"examples/imageclassification/pretrained_import.py": "torch"}


@pytest.mark.parametrize(
    "script",
    [pytest.param(p, marks=[pytest.mark.slow] if p in _SLOW else [])
     for p in EXAMPLES],
    ids=[os.path.basename(p) for p in EXAMPLES])
def test_example_smoke(script):
    if script in _NEEDS:
        pytest.importorskip(_NEEDS[script])
    env = dict(os.environ)
    # examples assume `pip install analytics-zoo-tpu`; in-tree CI runs them
    # against the checkout instead
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # the smoke runs on the CPU backend: the variable in the child's
    # environment is all it takes
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, script), "--smoke"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        f"{script} failed:\n--- stdout ---\n{proc.stdout[-2000:]}\n"
        f"--- stderr ---\n{proc.stderr[-2000:]}")
    assert proc.stdout.strip(), f"{script} produced no output"
