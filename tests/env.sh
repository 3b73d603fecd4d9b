# Shared environment for the shell test harnesses
# (equivalent of reference tests/env.sh:1-9).
export REPORTER_HOST=${REPORTER_HOST:-localhost}
export REPORTER_PORT=${REPORTER_PORT:-8002}
export REPORTER_URL=${REPORTER_URL:-http://${REPORTER_HOST}:${REPORTER_PORT}/report}
# synth sv layout: uuid|lat|lon|time|accuracy (tools/synth_cli.py emit_sv)
export FORMATTER=${FORMATTER:-',sv,\|,0,1,2,3,4'}
export REPORT_LEVELS=${REPORT_LEVELS:-0,1,2}
export TRANSITION_LEVELS=${TRANSITION_LEVELS:-0,1,2}
export THRESHOLD_SEC=${THRESHOLD_SEC:-15}
# test harnesses never contend for the real chip (conftest's rule, for
# shell entry points): run on the virtual CPU mesh
export REPORTER_TPU_PLATFORM=${REPORTER_TPU_PLATFORM:-cpu}
export REPORTER_TPU_VIRTUAL_DEVICES=${REPORTER_TPU_VIRTUAL_DEVICES:-8}
