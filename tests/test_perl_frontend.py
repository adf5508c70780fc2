"""Perl frontend (perl-package/): a second SCRIPTING-language binding
built purely on the flat C ABI — the capability row the reference's
R-package fills over its C API (reference R-package/src/ Rcpp layer).

The XS extension (perl-package/MXNetTPU.xs) is compiled once per module
with the stock Perl toolchain (ExtUtils::MakeMaker), then
perl-package/examples/train_mlp.pl builds an MLP symbol, binds an
executor, streams MNIST-format idx batches through MNISTIter, and
trains via KVStore SGD to ~1.0 accuracy — no Python in the frontend
process' source."""

import os
import shutil
import subprocess

import pytest

from test_native import _make_idx_dataset

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _have_perl_toolchain():
    if shutil.which("perl") is None:
        return False
    r = subprocess.run(
        ["perl", "-MConfig", "-MExtUtils::MakeMaker", "-e",
         "print $Config{archlibexp}"],
        capture_output=True, text=True)
    if r.returncode != 0:
        return False
    return os.path.exists(os.path.join(r.stdout.strip(), "CORE", "perl.h"))


@pytest.fixture(scope="module")
def perl_pkg(tmp_path_factory):
    """Out-of-tree build of the XS package, shared by every test in
    this module: (pkg_dir, env).  Copying the sources keeps MakeMaker's
    Makefile/blib out of the repo."""
    if not _have_perl_toolchain():
        pytest.skip("no perl XS toolchain")
    if not os.path.exists(os.path.join(REPO, "mxnet_tpu", "lib",
                                       "libmxtpu.so")):
        pytest.skip("libmxtpu.so not built")
    pkg = tmp_path_factory.mktemp("perl") / "perl-package"
    shutil.copytree(os.path.join(REPO, "perl-package"), pkg,
                    ignore=shutil.ignore_patterns(
                        "blib", "*.o", "*.c", "*.bs", "Makefile",
                        "Makefile.old", "MYMETA*", "pm_to_blib"))
    env = dict(os.environ)
    env["MXTPU_HOME"] = REPO
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    for cmd in (["perl", "Makefile.PL"], ["make"]):
        r = subprocess.run(cmd, cwd=pkg, env=env, capture_output=True,
                           text=True)
        assert r.returncode == 0, (r.stdout + "\n" + r.stderr)[-3000:]
    return pkg, env


@pytest.mark.slow
def test_perl_frontend_trains(perl_pkg, tmp_path):
    pkg, env = perl_pkg
    img_path, lab_path = _make_idx_dataset(tmp_path, seed=2)
    r = subprocess.run(
        ["perl", os.path.join(pkg, "examples", "train_mlp.pl"),
         img_path, lab_path, "50", "12"],
        env=env, capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, (r.stdout + "\n" + r.stderr)[-3000:]
    assert "PERL_TRAIN_OK" in r.stdout, r.stdout[-2000:]


@pytest.mark.slow
def test_perl_imperative_ops(perl_pkg):
    """Imperative NDArray ops from Perl via MXTPUFuncInvoke: ops are
    runtime-discovered (list_ops), with operator-overload sugar incl.
    scalar operands and clear croaks on misuse."""
    pkg, env = perl_pkg
    script = r'''
use blib; use MXNetTPU;
my $a = MXNetTPU::NDArray->new([2,2])->set_floats([1,2,3,4]);
my $b = MXNetTPU::NDArray->new([2,2])->set_floats([10,20,30,40]);
my $s = $a + $b;
die "add" unless join(",", @{$s->to_floats}) eq "11,22,33,44";
my $m = MXNetTPU::NDArray->invoke("_mul", [$a, $b]);
die "mul" unless join(",", @{$m->to_floats}) eq "10,40,90,160";
my $p = $a + 1;                       # scalar routes to _plus_scalar
die "plus_scalar" unless join(",", @{$p->to_floats}) eq "2,3,4,5";
my $r = 10 - $a;                      # swapped scalar -> _rminus_scalar
die "rminus" unless join(",", @{$r->to_floats}) eq "9,8,7,6";
eval { my $bad = $a + {}; };
die "croak" unless $@ =~ /operands must be NDArrays or numbers/;
die "ops" unless scalar(@{MXNetTPU::list_ops()}) > 100;
print "PERL_IMPERATIVE_OK\n";
'''
    r = subprocess.run(["perl", "-e", script], cwd=pkg, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (r.stdout + "\n" + r.stderr)[-2000:]
    assert "PERL_IMPERATIVE_OK" in r.stdout


@pytest.mark.slow
def test_perl_predict_serves_python_checkpoint(perl_pkg, tmp_path):
    """Cross-language serving: a checkpoint trained in Python loads and
    predicts from Perl through the predict mini-API, matching the
    Python predictor's outputs."""
    pkg, env = perl_pkg
    import numpy as np

    import mxnet_tpu as mx

    rng = np.random.RandomState(4)
    X = rng.randn(16, 6).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                              name="fc"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(X, y, 8), num_epoch=4,
            initializer=mx.initializer.Xavier())
    args, aux = mod.get_params()
    prefix = str(tmp_path / "ck")
    mx.model.save_checkpoint(prefix, 1, net, args, aux)
    ref = mx.predict.create(
        net.tojson(), {"arg:" + k: v for k, v in args.items()},
        {"data": X.shape})
    want = np.asarray(ref.forward(data=X)[0])

    floats = " ".join(str(float(v)) for v in X.reshape(-1))
    r = subprocess.run(
        ["perl", os.path.join(pkg, "examples", "predict.pl"),
         prefix, "1", "data", "16,6"],
        input=floats, env=env, capture_output=True, text=True,
        timeout=300)
    assert r.returncode == 0, (r.stdout + "\n" + r.stderr)[-2000:]
    assert "PERL_PREDICT_OK" in r.stdout
    row0 = [float(v) for v in
            r.stdout.split("row 0:")[1].splitlines()[0].split()]
    np.testing.assert_allclose(row0, want[0], rtol=1e-5, atol=1e-6)
