"""`python -m kubernetes_tpu.cli ...` — kubectl verbs, plus `cluster up` and
`apiserver`. Each imports its own module: the apiserver's imports no jax."""

import sys

if __name__ == "__main__":
    verb = sys.argv[1] if len(sys.argv) > 1 else ""
    if verb == "apiserver":
        from kubernetes_tpu.cli.apiserver import apiserver_main

        sys.exit(apiserver_main(sys.argv[2:]))
    if verb == "cluster":
        from kubernetes_tpu.cli.cluster import cluster_main

        sys.exit(cluster_main(sys.argv[2:]))
    from kubernetes_tpu.cli.kubectl import main

    sys.exit(main())
