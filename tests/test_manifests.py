"""IaC validation: every YAML parses, kustomizations reference real files,
the accelerator contract is TPU-only (zero NVIDIA components — the
BASELINE.json north star), and key parity invariants hold.

kubectl/kustomize aren't in this image, so this is a pure-Python structural
check (a minimal kustomize resolver), mirroring the reference's own lack of
manifest CI (SURVEY.md §4: its "tests" were README-driven smoke Jobs)."""

import os
from pathlib import Path

import yaml

REPO = Path(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CLUSTER = REPO / "cluster-config"


def _load_all(path: Path):
    with open(path) as f:
        return [d for d in yaml.safe_load_all(f) if d]


def all_yaml_files():
    return sorted(
        list(CLUSTER.rglob("*.yaml")) + list((REPO / "tpu-installation").rglob("*.yaml"))
    )


def all_cluster_docs():
    docs = []
    for p in CLUSTER.rglob("*.yaml"):
        for d in _load_all(p):
            docs.append((p, d))
    return docs


def test_every_yaml_parses():
    files = all_yaml_files()
    assert len(files) > 20, f"expected a full manifest tree, found {len(files)}"
    for p in files:
        docs = _load_all(p)
        assert docs, f"{p} parsed to nothing"


def test_kustomizations_reference_existing_files():
    for p in CLUSTER.rglob("kustomization.yaml"):
        for doc in _load_all(p):
            for res in doc.get("resources", []):
                target = p.parent / res
                assert target.exists(), f"{p}: missing resource {res}"


def test_zero_nvidia_components():
    """North star (BASELINE.json): zero NVIDIA components in-cluster."""
    for p, d in all_cluster_docs():
        text = yaml.safe_dump(d)
        assert "nvidia.com/gpu" not in text, f"{p} requests nvidia.com/gpu"
        assert "runtimeClassName" not in text, f"{p} uses a RuntimeClass (no TPU analog)"
        assert "nvcr.io" not in text, f"{p} references an NVIDIA registry image"


def test_tpu_resource_requests_present():
    """Every accelerator workload must request google.com/tpu."""
    tpu_requests = 0
    for p, d in all_cluster_docs():
        if d.get("kind") in ("Deployment", "Job", "JobSet"):
            text = yaml.safe_dump(d)
            if "google.com/tpu" in text:
                tpu_requests += 1
    assert tpu_requests >= 6, f"expected >=6 TPU workloads, found {tpu_requests}"


def test_flux_toolkit_is_complete():
    """`kubectl apply -k cluster-config/cluster/flux-system/` must install a
    RECONCILING cluster: the vendored gotk-components.yaml (upstream
    `flux install --export` output, like the reference vendors) has to carry
    the four controllers and their CRDs, not just the namespace."""
    docs = _load_all(CLUSTER / "cluster" / "flux-system" /
                     "gotk-components.yaml")
    kinds = {}
    for d in docs:
        kinds.setdefault(d["kind"], []).append(d["metadata"]["name"])
    deployments = set(kinds.get("Deployment", []))
    assert {"source-controller", "kustomize-controller", "helm-controller",
            "notification-controller"} <= deployments, deployments
    crds = set(kinds.get("CustomResourceDefinition", []))
    for crd in ("gitrepositories.source.toolkit.fluxcd.io",
                "kustomizations.kustomize.toolkit.fluxcd.io",
                "helmreleases.helm.toolkit.fluxcd.io",
                "helmrepositories.source.toolkit.fluxcd.io"):
        assert crd in crds, f"missing CRD {crd}"
    assert "Namespace" in kinds
    # the kustomization actually includes it
    kust = _load_all(CLUSTER / "cluster" / "flux-system" /
                     "kustomization.yaml")[0]
    assert "gotk-components.yaml" in kust["resources"]


def test_device_plugin_schedules_on_any_chip_count():
    """The installer labels nodes with the *actual* chip count
    (install-k8s-tpu.yaml), so the plugin must match label existence —
    an exact-value selector would never schedule on the 1-chip dev box."""
    ds = _load_all(CLUSTER / "apps" / "tpu-stack" /
                   "device-plugin-daemonset.yaml")[0]
    spec = ds["spec"]["template"]["spec"]
    assert "tpu.tpustack.dev/chips" not in spec.get("nodeSelector", {}), \
        "exact-value chips nodeSelector excludes non-8-chip nodes"
    terms = (spec["affinity"]["nodeAffinity"]
             ["requiredDuringSchedulingIgnoredDuringExecution"]
             ["nodeSelectorTerms"])
    exprs = [e for t in terms for e in t["matchExpressions"]]
    assert any(e["key"] == "tpu.tpustack.dev/chips" and
               e["operator"] == "Exists" for e in exprs)

    # simulate scheduling against both node shapes
    for labels in ({"tpu.tpustack.dev/chips": "1"},
                   {"tpu.tpustack.dev/chips": "8"}):
        ok = any(all(
            (e["operator"] == "Exists" and e["key"] in labels) or
            (e["operator"] == "In" and labels.get(e["key"]) in e["values"])
            for e in t["matchExpressions"]) for t in terms)
        assert ok, f"device plugin would not schedule on node {labels}"

    image = spec["containers"][0]["image"]
    assert ":latest" not in image and ":" in image.split("/")[-1], \
        f"device-plugin image must be version-pinned, got {image}"


def test_flux_fanout_dependencies():
    """Workload apps must depend on tpu-stack, like the reference's llm
    depended on nvidia (apps-kustomization.yaml:50-53)."""
    path = CLUSTER / "cluster" / "flux-system" / "apps-kustomization.yaml"
    docs = {d["metadata"]["name"]: d for d in _load_all(path)}
    assert set(docs) >= {"tpu-stack", "renovate", "sd15-api", "llm", "smoke-jobs"}
    for app in ("sd15-api", "llm", "smoke-jobs"):
        deps = [x["name"] for x in docs[app]["spec"].get("dependsOn", [])]
        assert "tpu-stack" in deps, f"{app} must dependsOn tpu-stack"
    for name, d in docs.items():
        assert d["spec"]["prune"] is True
        assert d["spec"]["sourceRef"]["name"] == "flux-system"


def test_sd15_service_keeps_nodeport_30800():
    """Client compatibility: reference NodePort 30800 (service.yaml:7-13)."""
    svc = _load_all(CLUSTER / "apps" / "sd15-api" / "service.yaml")[0]
    port = svc["spec"]["ports"][0]
    assert svc["spec"]["type"] == "NodePort"
    assert port["nodePort"] == 30800
    assert port["targetPort"] == 8000


def test_llm_ctx_parity():
    """Reference parity: llama.cpp --ctx-size 4096 (llm/deployment.yaml:67-68)."""
    dep = _load_all(CLUSTER / "apps" / "llm" / "deployment.yaml")[0]
    env = {e["name"]: e.get("value") for e in
           dep["spec"]["template"]["spec"]["containers"][0]["env"]
           if "value" in e}
    assert env["LLM_CTX"] == "4096"


def test_smoke_job_runs_vectoradd_module():
    docs = _load_all(CLUSTER / "jobs" / "jax-vectoradd.yaml")
    job = next(d for d in docs if d["kind"] == "Job")
    cmd = job["spec"]["template"]["spec"]["containers"][0]["command"]
    assert cmd[-1] == "tpustack.ops.vectoradd"
    assert job["spec"]["backoffLimit"] == 0


def test_isolation_job_two_parallel_pods():
    docs = _load_all(CLUSTER / "jobs" / "tpu-isolation-test.yaml")
    job = next(d for d in docs if d["kind"] == "Job")
    assert job["spec"]["completions"] == 2
    assert job["spec"]["parallelism"] == 2
    limits = job["spec"]["template"]["spec"]["containers"][0]["resources"]["limits"]
    assert limits["google.com/tpu"] == 1


def test_jobset_multihost_topology():
    docs = _load_all(CLUSTER / "jobs" / "train-llama2-jobset.yaml")
    js = next(d for d in docs if d["kind"] == "JobSet")
    tmpl = js["spec"]["replicatedJobs"][0]["template"]["spec"]
    assert tmpl["parallelism"] == 2 and tmpl["completions"] == 2
    pod = tmpl["template"]["spec"]["containers"][0]
    env = {e["name"] for e in pod["env"]}
    assert {"NUM_PROCESSES", "PROCESS_ID", "COORDINATOR_ADDRESS"} <= env
    assert pod["resources"]["limits"]["google.com/tpu"] == 8


def test_sd15_alt_helmrelease_self_contained():
    """The alternative chart path must not repeat the reference's dead-code bug
    (SURVEY.md §2.4: HelmRelease referencing a HelmRepository defined nowhere).
    Ours ships the HelmRepository in the same file and stays out of the
    kustomization, mirroring the reference's posture minus the bug."""
    path = CLUSTER / "apps" / "sd15-api" / "helmrelease.yaml"
    docs = _load_all(path)
    kinds = {d["kind"]: d for d in docs}
    assert {"HelmRepository", "HelmRelease"} <= set(kinds)
    src = kinds["HelmRelease"]["spec"]["chart"]["spec"]["sourceRef"]
    assert src["name"] == kinds["HelmRepository"]["metadata"]["name"]
    kust = _load_all(CLUSTER / "apps" / "sd15-api" / "kustomization.yaml")[0]
    assert "helmrelease.yaml" not in kust["resources"]
    # same TPU contract as the Deployment path
    text = yaml.safe_dump(kinds["HelmRelease"])
    assert "google.com/tpu" in text and "30800" in text


def test_renovate_markers_match_config_regex():
    """Every `# renovate:` marker must actually match the regex manager in
    renovate.json (the reference's only enabled manager, renovate.json:11),
    and every marked file must be in managerFilePatterns."""
    import json
    import re

    conf = json.loads((REPO / "renovate.json").read_text())

    def compile_file_pattern(p):
        """Renovate ≥40 managerFilePatterns: `/…/` wrapping marks a regex
        (optionally `!`-negated); bare strings are minimatch globs, which
        this repo avoids — enforce the unambiguous regex form."""
        negate = p.startswith("!")
        body = p[1:] if negate else p
        assert body.startswith("/") and body.endswith("/"), (
            f"renovate pattern {p!r} must be slash-wrapped regex form")
        return negate, re.compile(body[1:-1])

    def file_matches(rel, pats):
        compiled = [compile_file_pattern(p) for p in pats]
        pos = [rx for neg, rx in compiled if not neg]
        negs = [rx for neg, rx in compiled if neg]
        return (any(rx.search(rel) for rx in pos)
                and not any(rx.search(rel) for rx in negs))

    managers = []
    for mgr in conf["customManagers"]:
        # renovate matchStrings are ECMAScript regexes: (?<name>…) → (?P<name>…)
        regexes = [re.compile(re.sub(r"\(\?<([A-Za-z]+)>", r"(?P<\1>", s))
                   for s in mgr["matchStrings"]]
        managers.append((mgr["managerFilePatterns"], regexes))
    # kubernetes-manager patterns must be well-formed too, and must exclude
    # the files a custom manager owns plus the vendored flux toolkit
    k8s_pats = conf["kubernetes"]["managerFilePatterns"]
    for p in k8s_pats:
        compile_file_pattern(p)
    assert not file_matches(
        "cluster-config/apps/tpu-stack/device-plugin-daemonset.yaml", k8s_pats)
    assert not file_matches(
        "cluster-config/cluster/flux-system/gotk-components.yaml", k8s_pats)
    assert file_matches("cluster-config/apps/llm/deployment.yaml", k8s_pats)

    marked = []
    for p in all_yaml_files():
        text = p.read_text()
        if "# renovate:" not in text:
            continue
        rel = str(p.relative_to(REPO))
        applicable = [rx for pats, rxs in managers
                      if file_matches(rel, pats) for rx in rxs]
        assert applicable, (
            f"{rel} has renovate markers but matches no manager's file patterns")
        hits = [m for rx in applicable for m in rx.finditer(text)]
        assert len(hits) == text.count("# renovate:"), (
            f"{rel}: marker(s) present that the matchStrings regexes miss "
            f"(or double-match): {len(hits)} hits vs "
            f"{text.count('# renovate:')} markers")
        marked.extend(m.group("depName") for m in hits)
    assert {"kubernetes/kubernetes", "kubernetes-sigs/jobset", "libtpu",
            "gcr.io/gke-release/tpu-device-plugin"} <= set(marked)
    # digest pinning is on for container images, so the tag pin above gets a
    # digest lock on renovate's first online run
    assert any(r.get("pinDigests") for r in conf.get("packageRules", []))


def test_ansible_playbook_shapes():
    """3-playbook surface parity with rke2-installation (SURVEY.md §2.1)."""
    inst = REPO / "tpu-installation"
    for name in ("install-k8s-tpu.yaml", "fetch-kubeconfig.yaml",
                 "uninstall-k8s-tpu.yaml"):
        docs = _load_all(inst / name)
        plays = [p for doc in docs for p in (doc if isinstance(doc, list) else [doc])]
        assert plays and all("hosts" in p for p in plays), f"{name} not a playbook"
    gv = _load_all(inst / "group_vars" / "all.yaml")[0]
    assert "kubernetes_version" in gv and "libtpu_version" in gv
    inventory = (inst / "inventory.ini").read_text()
    assert "[masters]" in inventory and "k8s_cluster:children" in inventory


# ---------------------------------------------------------- observability
def _pod_template(doc):
    if doc["kind"] == "JobSet":  # replicatedJobs[].template is a Job spec
        return doc["spec"]["replicatedJobs"][0]["template"]["spec"]["template"]
    return doc["spec"]["template"]


def test_serving_pods_carry_scrape_annotations():
    """Every serving Deployment's pod template must be scrapeable: the
    prometheus.io annotation trio, with the port matching the serving
    containerPort (where /metrics actually listens)."""
    targets = [
        (CLUSTER / "apps" / "sd15-api" / "deployment.yaml", "sd15-api"),
        (CLUSTER / "apps" / "llm" / "deployment.yaml", "coder-llm"),
        (CLUSTER / "apps" / "llm" / "wan-deployment.yaml", "wan-video-gen"),
    ]
    for path, name in targets:
        dep = next(d for d in _load_all(path) if d["kind"] == "Deployment")
        assert dep["metadata"]["name"] == name
        tmpl = dep["spec"]["template"]
        ann = tmpl["metadata"].get("annotations", {})
        assert ann.get("prometheus.io/scrape") == "true", f"{path}: scrape off"
        assert ann.get("prometheus.io/path") == "/metrics", path
        ports = [p["containerPort"]
                 for c in tmpl["spec"]["containers"]
                 for p in c.get("ports", [])]
        assert int(ann["prometheus.io/port"]) in ports, (
            f"{path}: annotation port {ann['prometheus.io/port']} not a "
            f"containerPort {ports}")


def test_batch_jobs_scrape_wiring():
    """Jobs that run tpustack entrypoints expose the stdlib /metrics
    sidecar: TPUSTACK_METRICS_PORT env and matching scrape annotations."""
    job_files = ["batch-generate.yaml", "train-bert-v5e8.yaml",
                 "train-resnet50.yaml", "train-sd15.yaml",
                 "train-llama2-jobset.yaml"]
    for name in job_files:
        docs = _load_all(CLUSTER / "jobs" / name)
        doc = next(d for d in docs if d["kind"] in ("Job", "JobSet"))
        tmpl = _pod_template(doc)
        ann = tmpl["metadata"].get("annotations", {})
        assert ann.get("prometheus.io/scrape") == "true", f"{name}: scrape off"
        port = ann.get("prometheus.io/port")
        assert port, f"{name}: no scrape port"
        env = {e["name"]: e.get("value")
               for c in tmpl["spec"]["containers"] for e in c.get("env", [])}
        assert env.get("TPUSTACK_METRICS_PORT") == port, (
            f"{name}: TPUSTACK_METRICS_PORT ({env.get('TPUSTACK_METRICS_PORT')})"
            f" must match the scrape annotation ({port})")


def test_podmonitoring_selects_real_workloads():
    """The GMP-flavour scrape CRs must target labels/ports that actually
    exist on the Deployments they monitor, in the right namespace."""
    mon = CLUSTER / "apps" / "monitoring"
    kust = _load_all(mon / "kustomization.yaml")[0]
    assert len(kust["resources"]) >= 3
    deployments = {}
    for p in [CLUSTER / "apps" / "sd15-api" / "deployment.yaml",
              CLUSTER / "apps" / "llm" / "deployment.yaml",
              CLUSTER / "apps" / "llm" / "wan-deployment.yaml"]:
        for d in _load_all(p):
            if d["kind"] == "Deployment":
                deployments[d["metadata"]["name"]] = d
    seen = 0
    for res in kust["resources"]:
        for pm in _load_all(mon / res):
            if pm["kind"] != "PodMonitoring":
                # the monitoring dir also carries the SLO rule CRs —
                # validated structurally by tools/lint_manifests.py
                continue
            sel = pm["spec"]["selector"]["matchLabels"]
            match = [d for d in deployments.values()
                     if d["metadata"]["namespace"] == pm["metadata"]["namespace"]
                     and all(d["spec"]["template"]["metadata"]["labels"].get(k) == v
                             for k, v in sel.items())]
            assert match, f"{res}: selector {sel} matches no Deployment"
            port_names = {p.get("name")
                          for c in match[0]["spec"]["template"]["spec"]["containers"]
                          for p in c.get("ports", [])}
            for ep in pm["spec"]["endpoints"]:
                assert ep["path"] == "/metrics", res
                assert ep["port"] in port_names, (
                    f"{res}: endpoint port {ep['port']!r} is not a named "
                    f"containerPort {port_names}")
            seen += 1
    assert seen >= 3


def test_slo_rules_and_prober_wired():
    """The SLO layer is reconciled: rules in the monitoring kustomization
    with the multi-window burn-rate alert pairs + prober alerts, and the
    prober CronJob in the jobs kustomization targeting all three
    Services."""
    mon = CLUSTER / "apps" / "monitoring"
    kust = _load_all(mon / "kustomization.yaml")[0]
    assert "slo-rules.yaml" in kust["resources"]
    rules = _load_all(mon / "slo-rules.yaml")[0]
    alerts = {r["alert"] for g in rules["spec"]["groups"]
              for r in g["rules"] if "alert" in r}
    assert {"TpustackAvailabilityFastBurn", "TpustackAvailabilitySlowBurn",
            "TpustackLatencyFastBurn", "TpustackLatencySlowBurn",
            "TpustackProbeDown", "TpustackProbeStale"} <= alerts
    jobs_kust = _load_all(CLUSTER / "jobs" / "kustomization.yaml")[0]
    assert "prober-cronjob.yaml" in jobs_kust["resources"]
    prober = _load_all(CLUSTER / "jobs" / "prober-cronjob.yaml")[0]
    cmd = " ".join(prober["spec"]["jobTemplate"]["spec"]["template"]["spec"]
                   ["containers"][0]["command"])
    for flag in ("--llm=", "--sd=", "--graph="):
        assert flag in cmd, cmd


def test_flux_monitoring_kustomization_wired():
    """The monitoring app rides the same Flux fan-out, after its targets."""
    path = CLUSTER / "cluster" / "flux-system" / "apps-kustomization.yaml"
    docs = {d["metadata"]["name"]: d for d in _load_all(path)}
    assert "monitoring" in docs
    mon = docs["monitoring"]["spec"]
    assert mon["path"] == "./cluster-config/apps/monitoring"
    deps = [x["name"] for x in mon.get("dependsOn", [])]
    assert {"sd15-api", "llm"} <= set(deps)


def test_persistent_compile_cache_wired_into_serving_pods():
    """Every TPU serving Deployment (llm, wan, sd15) must set
    JAX_COMPILATION_CACHE_DIR (JAX's own variable — the one spelling
    ``tpustack.utils.enable_compile_cache`` honours) to a path under a
    mounted volume, so pod restarts reuse compiled programs instead of
    paying the multi-minute cold jit again."""
    serving = [CLUSTER / "apps" / "llm" / "deployment.yaml",
               CLUSTER / "apps" / "llm" / "wan-deployment.yaml",
               CLUSTER / "apps" / "sd15-api" / "deployment.yaml"]
    for p in serving:
        deps = [d for d in _load_all(p) if d.get("kind") == "Deployment"]
        assert deps, f"{p}: no Deployment doc"
        for d in deps:
            containers = d["spec"]["template"]["spec"]["containers"]
            server = containers[0]
            env = {e["name"]: e.get("value") for e in server.get("env", [])}
            cache = env.get("JAX_COMPILATION_CACHE_DIR")
            assert cache, (f"{p}: server container missing "
                           "JAX_COMPILATION_CACHE_DIR")
            mounts = [m["mountPath"] for m in server.get("volumeMounts", [])]
            assert any(cache == m or cache.startswith(m.rstrip("/") + "/")
                       for m in mounts), (
                f"{p}: JAX_COMPILATION_CACHE_DIR={cache} is not under any "
                f"volumeMount {mounts} — the cache would die with the pod")
    # the HelmRelease variant carries the same contract through values
    hr = _load_all(CLUSTER / "apps" / "sd15-api" / "helmrelease.yaml")
    text = yaml.safe_dump(hr)
    assert "JAX_COMPILATION_CACHE_DIR" in text


# ------------------------------------------------------------ resilience
def _import_lint_manifests():
    import sys

    sys.path.insert(0, str(REPO / "tools"))
    try:
        import lint_manifests
    finally:
        sys.path.pop(0)
    return lint_manifests


def test_manifest_lint_green():
    assert _import_lint_manifests().lint() == []


# NOTE: the CLI shell-out moved to tests/test_tpulint.py::
# test_repo_lints_clean_cli — lint_manifests is now the TPL601 checker
# under `python -m tools.tpulint`, and that one subprocess run covers it
# (tools/lint_manifests.py remains a shim; its lint() import contract is
# what the tests here keep exercising).


def test_manifest_lint_catches_violations(tmp_path):
    """A Deployment with no probes, no cpu/memory resources, and a grace
    period shorter than its declared drain budget trips every rule."""
    bad = {
        "apiVersion": "apps/v1", "kind": "Deployment",
        "metadata": {"name": "bad", "namespace": "x"},
        "spec": {"template": {"spec": {
            "terminationGracePeriodSeconds": 10,
            "containers": [{
                "name": "srv",
                "env": [{"name": "TPUSTACK_DRAIN_TIMEOUT_S",
                         "value": "30"}],
                "resources": {"limits": {"google.com/tpu": 1}},
            }],
        }}},
    }
    (tmp_path / "bad.yaml").write_text(yaml.safe_dump(bad))
    errors = _import_lint_manifests().lint(root=tmp_path)
    joined = "\n".join(errors)
    for frag in ("readinessProbe", "livenessProbe", "requests.cpu",
                 "limits.memory", "preStop", "SIGKILL the pod mid-drain"):
        assert frag in joined, (frag, joined)


def test_serving_deployments_declare_drain_contract():
    """All three serving Deployments: drain env present, readiness on
    /readyz, liveness on /healthz, preStop hook, and a grace period that
    covers preStop + drain (the SIGKILL-mid-drain guard)."""
    serving = [CLUSTER / "apps" / "llm" / "deployment.yaml",
               CLUSTER / "apps" / "llm" / "wan-deployment.yaml",
               CLUSTER / "apps" / "sd15-api" / "deployment.yaml"]
    for p in serving:
        dep = next(d for d in _load_all(p) if d.get("kind") == "Deployment")
        spec = dep["spec"]["template"]["spec"]
        server = spec["containers"][0]
        env = {e["name"]: e.get("value") for e in server.get("env", [])}
        drain = float(env["TPUSTACK_DRAIN_TIMEOUT_S"])
        assert float(env["TPUSTACK_REQUEST_TIMEOUT_S"]) > 0, p
        assert int(env["TPUSTACK_MAX_QUEUE_DEPTH"]) > 0, p
        assert float(env["TPUSTACK_WATCHDOG_S"]) > 0, p
        assert server["readinessProbe"]["httpGet"]["path"] == "/readyz", p
        assert server["livenessProbe"]["httpGet"]["path"] == "/healthz", p
        assert "startupProbe" in server, p
        assert server["lifecycle"]["preStop"], p
        assert spec["terminationGracePeriodSeconds"] >= drain + 5, p


def test_llm_prefix_cache_knobs_declared():
    """The LLM Deployment pins the prefix-KV-cache contract explicitly so
    operators see (and can tune) it in IaC, not just in code defaults."""
    for d in _load_all(CLUSTER / "apps" / "llm" / "deployment.yaml"):
        if d.get("kind") != "Deployment":
            continue
        env = {e["name"]: e.get("value")
               for e in d["spec"]["template"]["spec"]["containers"][0]["env"]}
        assert env.get("TPUSTACK_PREFIX_CACHE") == "1"
        assert float(env["TPUSTACK_PREFIX_CACHE_MB"]) > 0
        assert int(env["TPUSTACK_PREFIX_CACHE_CHUNK"]) > 0


def test_router_fronts_scaled_out_llm_replicas():
    """The scale-out pairing: >1 llm replica, a headless per-pod Service
    the router discovers backends through (dns://), and a stable VIP
    Service clients point at."""
    docs = _load_all(CLUSTER / "apps" / "llm" / "router-deployment.yaml")
    headless = next(d for d in docs if d.get("kind") == "Service"
                    and d["metadata"]["name"] == "coder-llm-pods")
    assert str(headless["spec"]["clusterIP"]) == "None"  # headless
    assert headless["spec"]["selector"] == {"app": "coder-llm"}
    assert headless["spec"]["publishNotReadyAddresses"] is True

    router = next(d for d in docs if d.get("kind") == "Deployment")
    srv = router["spec"]["template"]["spec"]["containers"][0]
    assert "tpustack.serving.router" in " ".join(srv["command"])
    env = {e["name"]: e.get("value") for e in srv["env"]}
    assert env["TPUSTACK_ROUTER_BACKENDS"].startswith(
        "dns://coder-llm-pods.")
    assert srv["readinessProbe"]["httpGet"]["path"] == "/readyz"
    assert srv["livenessProbe"]["httpGet"]["path"] == "/healthz"
    assert "google.com/tpu" not in (srv["resources"].get("limits") or {})

    vip = next(d for d in docs if d.get("kind") == "Service"
               and d["metadata"]["name"] == "coder-llm-router")
    assert vip["spec"]["selector"] == {"app": "coder-llm-router"}

    llm = next(d for d in _load_all(CLUSTER / "apps" / "llm"
                                    / "deployment.yaml")
               if d.get("kind") == "Deployment")
    assert llm["spec"]["replicas"] > 1


def test_manifest_lint_catches_router_violations(tmp_path):
    """The TPL601 router pairing rule: scaled-out llm replicas without a
    router, a router with no backends, a dns:// spec pointing at a
    missing or non-headless Service."""
    lint = _import_lint_manifests().lint
    llm = {
        "apiVersion": "apps/v1", "kind": "Deployment",
        "metadata": {"name": "llm", "namespace": "x"},
        "spec": {"replicas": 3, "template": {
            "metadata": {"labels": {"app": "llm"}},
            "spec": {"terminationGracePeriodSeconds": 45, "containers": [{
                "name": "srv",
                "command": ["python", "-m", "tpustack.serving.llm_server"],
                "resources": {"requests": {"cpu": 1, "memory": "1Gi"},
                              "limits": {"cpu": 1, "memory": "1Gi"}},
                "readinessProbe": {"httpGet": {"path": "/readyz"}},
                "livenessProbe": {"httpGet": {"path": "/healthz"}},
            }]},
        }}}

    (tmp_path / "llm.yaml").write_text(yaml.safe_dump(llm))
    errors = "\n".join(lint(root=tmp_path))
    assert "no router Deployment" in errors

    def router(backends):
        env = ([{"name": "TPUSTACK_ROUTER_BACKENDS", "value": backends}]
               if backends else [])
        return {
            "apiVersion": "apps/v1", "kind": "Deployment",
            "metadata": {"name": "router", "namespace": "x"},
            "spec": {"template": {
                "metadata": {"labels": {"app": "router"}},
                "spec": {"terminationGracePeriodSeconds": 45,
                         "containers": [{
                             "name": "router",
                             "command": ["python", "-m",
                                         "tpustack.serving.router"],
                             "env": env,
                             "resources": {
                                 "requests": {"cpu": 1, "memory": "1Gi"},
                                 "limits": {"cpu": 1, "memory": "1Gi"}},
                             "readinessProbe": {
                                 "httpGet": {"path": "/readyz"}},
                             "livenessProbe": {
                                 "httpGet": {"path": "/healthz"}},
                         }]},
            }}}

    (tmp_path / "router.yaml").write_text(yaml.safe_dump(router(None)))
    errors = "\n".join(lint(root=tmp_path))
    assert "constructs nothing" in errors
    assert "no router Deployment" not in errors  # pairing satisfied

    (tmp_path / "router.yaml").write_text(yaml.safe_dump(
        router("dns://llm-pods.x.svc.cluster.local:8080")))
    errors = "\n".join(lint(root=tmp_path))
    assert "no manifest defines" in errors

    svc = {
        "apiVersion": "v1", "kind": "Service",
        "metadata": {"name": "llm-pods", "namespace": "x"},
        "spec": {"clusterIP": "10.0.0.1", "selector": {"app": "llm"},
                 "ports": [{"port": 8080, "targetPort": 8080}]},
    }
    (tmp_path / "svc.yaml").write_text(yaml.safe_dump(svc))
    errors = "\n".join(lint(root=tmp_path))
    assert "not headless" in errors

    svc["spec"]["clusterIP"] = None
    svc["spec"]["selector"] = {"app": "nothing-has-this-label"}
    (tmp_path / "svc.yaml").write_text(yaml.safe_dump(svc))
    errors = "\n".join(lint(root=tmp_path))
    assert "matches no Deployment" in errors

    svc["spec"]["selector"] = {"app": "llm"}
    svc["spec"]["ports"] = [{"port": 80, "targetPort": 9999}]
    (tmp_path / "svc.yaml").write_text(yaml.safe_dump(svc))
    errors = "\n".join(lint(root=tmp_path))
    assert "port 8080 is not served" in errors

    svc["spec"]["ports"] = [{"port": 80, "targetPort": 8080}]
    (tmp_path / "svc.yaml").write_text(yaml.safe_dump(svc))
    assert lint(root=tmp_path) == []


# ------------------------------------------------- elastic capacity (PR 19)
def test_autoscaler_deployment_wired():
    """The shipped elastic-capacity controller: least-privilege RBAC
    (deployments/scale get+patch only, own namespace), pinned capacity
    bounds, the managed-by annotation on its target, kustomization and
    prober wiring."""
    docs = _load_all(CLUSTER / "apps" / "llm" / "autoscaler-deployment.yaml")
    kinds = {}
    for d in docs:
        kinds.setdefault(d["kind"], []).append(d)
    role = kinds["Role"][0]
    assert role["rules"] == [{"apiGroups": ["apps"],
                              "resources": ["deployments/scale"],
                              "verbs": ["get", "patch"]}]
    binding = kinds["RoleBinding"][0]
    assert binding["roleRef"]["kind"] == "Role"
    assert binding["subjects"][0]["name"] == \
        kinds["ServiceAccount"][0]["metadata"]["name"]

    dep = kinds["Deployment"][0]
    spec = dep["spec"]["template"]["spec"]
    ctr = spec["containers"][0]
    assert "tpustack.serving.autoscaler" in " ".join(ctr["command"])
    assert spec["serviceAccountName"] == \
        kinds["ServiceAccount"][0]["metadata"]["name"]
    env = {e["name"]: e.get("value") for e in ctr["env"]}
    assert int(env["TPUSTACK_AUTOSCALER_MIN"]) >= 1
    assert (int(env["TPUSTACK_AUTOSCALER_MAX"])
            >= int(env["TPUSTACK_AUTOSCALER_MIN"]))
    # scales its OWN namespace, and the target carries the marker
    assert env["TPUSTACK_AUTOSCALER_K8S_NAMESPACE"] == \
        dep["metadata"]["namespace"]
    llm = next(d for d in _load_all(CLUSTER / "apps" / "llm"
                                    / "deployment.yaml")
               if d.get("kind") == "Deployment")
    assert llm["metadata"]["name"] == env["TPUSTACK_AUTOSCALER_K8S_DEPLOYMENT"]
    assert llm["metadata"]["annotations"][
        "tpustack.dev/managed-by-autoscaler"] == "true"
    # no TPU for the control loop; riding the flux fan-out; probed
    assert "google.com/tpu" not in yaml.safe_dump(dep)
    kust = _load_all(CLUSTER / "apps" / "llm" / "kustomization.yaml")[0]
    assert "autoscaler-deployment.yaml" in kust["resources"]
    prober = _load_all(CLUSTER / "jobs" / "prober-cronjob.yaml")[0]
    cmd = " ".join(prober["spec"]["jobTemplate"]["spec"]["template"]["spec"]
                   ["containers"][0]["command"])
    assert "--autoscaler=http://coder-llm-autoscaler" in cmd


def _autoscaler_fixture(tmp_path, yaml_mod):
    """A minimal CLEAN autoscaler config in tmp_path; tests permute it."""
    def container(name, module, env):
        return {
            "name": name,
            "command": ["python", "-m", module],
            "env": [{"name": k, "value": v} for k, v in env.items()],
            "resources": {"requests": {"cpu": 1, "memory": "1Gi"},
                          "limits": {"cpu": 1, "memory": "1Gi"}},
            "readinessProbe": {"httpGet": {"path": "/readyz"}},
            "livenessProbe": {"httpGet": {"path": "/healthz"}},
        }

    llm = {
        "apiVersion": "apps/v1", "kind": "Deployment",
        "metadata": {"name": "llm", "namespace": "x",
                     "annotations":
                     {"tpustack.dev/managed-by-autoscaler": "true"}},
        "spec": {"replicas": 1, "template": {
            "metadata": {"labels": {"app": "llm"}},
            "spec": {"terminationGracePeriodSeconds": 45,
                     "containers": [container(
                         "srv", "tpustack.serving.llm_server", {})]},
        }}}
    scaler = {
        "apiVersion": "apps/v1", "kind": "Deployment",
        "metadata": {"name": "scaler", "namespace": "x"},
        "spec": {"template": {
            "metadata": {"labels": {"app": "scaler"}},
            "spec": {"terminationGracePeriodSeconds": 30,
                     "serviceAccountName": "scaler",
                     "containers": [container(
                         "ctl", "tpustack.serving.autoscaler", {
                             "TPUSTACK_AUTOSCALER_MIN": "1",
                             "TPUSTACK_AUTOSCALER_MAX": "4",
                             "TPUSTACK_AUTOSCALER_K8S_DEPLOYMENT": "llm",
                             "TPUSTACK_AUTOSCALER_K8S_NAMESPACE": "x",
                         })]},
        }}}
    role = {
        "apiVersion": "rbac.authorization.k8s.io/v1", "kind": "Role",
        "metadata": {"name": "scaler", "namespace": "x"},
        "rules": [{"apiGroups": ["apps"],
                   "resources": ["deployments/scale"],
                   "verbs": ["get", "patch"]}],
    }
    binding = {
        "apiVersion": "rbac.authorization.k8s.io/v1", "kind": "RoleBinding",
        "metadata": {"name": "scaler", "namespace": "x"},
        "roleRef": {"apiGroup": "rbac.authorization.k8s.io",
                    "kind": "Role", "name": "scaler"},
        "subjects": [{"kind": "ServiceAccount", "name": "scaler",
                      "namespace": "x"}],
    }

    def write(**overrides):
        docs = {"llm": llm, "scaler": scaler, "role": role,
                "binding": binding}
        docs.update(overrides)
        for fname, doc in docs.items():
            p = tmp_path / f"{fname}.yaml"
            if doc is None:
                if p.exists():
                    p.unlink()
            else:
                p.write_text(yaml_mod.safe_dump(doc))
    return llm, scaler, role, binding, write


def test_manifest_lint_catches_autoscaler_violations(tmp_path):
    """TPL601 elastic-capacity rules, fire and clean: RBAC must grant
    deployments/scale get+patch and nothing else, bounds pinned with
    MIN >= 1, own-namespace targeting, annotated target."""
    import copy

    lint = _import_lint_manifests().lint
    llm, scaler, role, binding, write = _autoscaler_fixture(tmp_path, yaml)

    write()
    assert lint(root=tmp_path) == []  # the clean baseline

    def env_of(doc):
        return doc["spec"]["template"]["spec"]["containers"][0]["env"]

    # MIN=0: scale-to-zero floor
    s = copy.deepcopy(scaler)
    env_of(s)[0]["value"] = "0"
    write(scaler=s)
    assert "scale-to-zero retires the entire fleet" in \
        "\n".join(lint(root=tmp_path))

    # bounds not pinned at all
    s = copy.deepcopy(scaler)
    env_of(s)[:] = env_of(s)[2:]
    write(scaler=s)
    assert "must pin TPUSTACK_AUTOSCALER_MIN" in "\n".join(lint(root=tmp_path))

    # cross-namespace targeting
    s = copy.deepcopy(scaler)
    env_of(s)[3]["value"] = "other"
    write(scaler=s)
    out = "\n".join(lint(root=tmp_path))
    assert "cross-namespace scaling" in out

    # Role grants more than deployments/scale get+patch
    r = copy.deepcopy(role)
    r["rules"][0]["verbs"] = ["get", "patch", "update"]
    write(role=r)
    assert "blast radius must stay at fleet size" in \
        "\n".join(lint(root=tmp_path))
    r = copy.deepcopy(role)
    r["rules"][0]["resources"] = ["deployments/scale", "secrets"]
    write(role=r)
    assert "blast radius must stay at fleet size" in \
        "\n".join(lint(root=tmp_path))

    # Role grants too little (patch without get): can't execute
    r = copy.deepcopy(role)
    r["rules"][0]["verbs"] = ["patch"]
    write(role=r)
    assert "could never execute a decision" in "\n".join(lint(root=tmp_path))

    # no RoleBinding at all → the PATCH would 403
    write(binding=None)
    assert "would 403" in "\n".join(lint(root=tmp_path))

    # ClusterRole-shaped grant is over-broad by construction
    b = copy.deepcopy(binding)
    b["roleRef"]["kind"] = "ClusterRole"
    write(binding=b)
    assert "cluster-scoped grants" in "\n".join(lint(root=tmp_path))

    # default ServiceAccount
    s = copy.deepcopy(scaler)
    del s["spec"]["template"]["spec"]["serviceAccountName"]
    write(scaler=s)
    assert "default ServiceAccount" in "\n".join(lint(root=tmp_path))

    # target Deployment missing / missing the managed-by marker
    write(llm=None)
    assert "no manifest defines" in "\n".join(lint(root=tmp_path))
    d = copy.deepcopy(llm)
    del d["metadata"]["annotations"]
    write(llm=d)
    assert "must carry" in "\n".join(lint(root=tmp_path))


def test_manifest_lint_catches_replicas_pins(tmp_path):
    """A kustomize patch (or replicas transformer) pinning replicas on an
    autoscaler-managed Deployment makes kustomize and the controller
    fight — fire on every patch flavour, stay clean on benign patches."""
    lint = _import_lint_manifests().lint
    _, _, _, _, write = _autoscaler_fixture(tmp_path, yaml)
    write()

    kust = {
        "apiVersion": "kustomize.config.k8s.io/v1beta1",
        "kind": "Kustomization",
        "resources": ["llm.yaml", "scaler.yaml", "role.yaml",
                      "binding.yaml"],
    }

    def kustomize(extra):
        doc = dict(kust, **extra)
        (tmp_path / "kustomization.yaml").write_text(yaml.safe_dump(doc))

    # benign patch: no replicas touched
    kustomize({"patches": [{"patch": yaml.safe_dump(
        {"apiVersion": "apps/v1", "kind": "Deployment",
         "metadata": {"name": "llm",
                      "annotations": {"x": "y"}}})}]})
    assert lint(root=tmp_path) == []

    # strategic-merge inline patch pinning replicas
    kustomize({"patches": [{"patch": yaml.safe_dump(
        {"apiVersion": "apps/v1", "kind": "Deployment",
         "metadata": {"name": "llm"}, "spec": {"replicas": 5}})}]})
    assert "fight over the fleet" in "\n".join(lint(root=tmp_path))

    # JSON6902 op list with a target
    kustomize({"patches": [{
        "target": {"kind": "Deployment", "name": "llm"},
        "patch": yaml.safe_dump(
            [{"op": "replace", "path": "/spec/replicas", "value": 5}]),
    }]})
    assert "fight over the fleet" in "\n".join(lint(root=tmp_path))

    # file-based patchesStrategicMerge (a partial-Deployment overlay is
    # not a standalone manifest — .yml keeps it out of the doc walk,
    # exactly how kustomize users keep overlays from double-applying)
    (tmp_path / "pin.yml").write_text(yaml.safe_dump(
        {"apiVersion": "apps/v1", "kind": "Deployment",
         "metadata": {"name": "llm"}, "spec": {"replicas": 5}}))
    kustomize({"patchesStrategicMerge": ["pin.yml"]})
    assert "fight over the fleet" in "\n".join(lint(root=tmp_path))

    # the replicas transformer
    kustomize({"replicas": [{"name": "llm", "count": 5}]})
    assert "replicas transformer pins" in "\n".join(lint(root=tmp_path))

    # pinning some OTHER deployment is fine
    kustomize({"replicas": [{"name": "unmanaged", "count": 5}]})
    assert lint(root=tmp_path) == []
