"""The reader of ``conv_ms`` (LeNet's convolutions, scoped
``lenet_conv``) on a made trace, found by name as the harness finds it."""
import pytest
from test_phase_metrics import BODY, _ms, _read

OPS = [
    (3, BODY + "fed_step/batches/gather"),
    (7, BODY + "fed_step/eval/cond/branch_1_fun/lenet_conv/convolution"),
    (11, BODY + "fed_step/local_train/while/body/closed_call/vmap()/while"
     "/body/closed_call/jvp(lenet_conv)/convolution"),
    (13, BODY + "fed_step/local_train/while/body/closed_call/vmap()/while"
     "/body/closed_call/transpose(jvp(lenet_conv))/convolution"),
    (17, BODY + "fed_step/recluster/cond/branch_1_fun/maml/while/body/"
     "closed_call/vmap(transpose(jvp(lenet_conv)))/add_any"),
    (19, BODY + "fed_step/local_train/lenet_conv_extra/add"),
    (23, BODY + "async_event/eval/cond/branch_1_fun/lenet_conv/add"),
    (29, BODY + "fed_step/local_train/conv_patches/concatenate"),
]


@pytest.mark.parametrize("name", ["conv_ms.sync", "conv_ms.async"])
def test_conv_bare_and_under_transforms(name):
    # eval (bare), jvp, transpose(jvp), vmap(transpose(jvp)), eval async;
    # ``lenet_conv_extra`` and the im2col scope are other scopes
    assert _read(name, OPS) == _ms(7, 11, 13, 17, 23)


@pytest.mark.parametrize("name", ["conv_ms.sync", "conv_ms.async"])
def test_conv_without_its_scope_reads_nothing(name):
    # a program whose LeNet builds im2col patches emits no such scope
    assert _read(name, [(3, BODY + "fed_step/local_train/conv_patches/"
                                  "concatenate")]) is None
