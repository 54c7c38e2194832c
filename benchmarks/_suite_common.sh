# Shared helper for the on-chip suite scripts. Source from a script
# that has set LOG (the append-target) — and optionally T (per-step
# timeout seconds, default 1800).
#
# A chip belongs to one process at a time, so a suite is a SEQUENCE of
# processes: each step ends before the next starts.
T=${T:-1800}

# pipeline status would be tee's, not the command's (POSIX sh has no
# PIPESTATUS) — capture the real rc via a temp file so a crash or a
# timeout is loudly marked in the log instead of reading as a silently
# truncated success. grep runs --line-buffered so the log shows live
# progress.
step() {
    echo "=== $* ===" | tee -a "$LOG"
    rcfile=$(mktemp)
    { timeout "$T" "$@" 2>&1; echo $? > "$rcfile"; } \
        | grep --line-buffered -v "WARNING" | tee -a "$LOG"
    rc=$(cat "$rcfile"); rm -f "$rcfile"
    if [ "$rc" != "0" ]; then
        echo "=== FAILED rc=$rc (124=timeout): $* ===" | tee -a "$LOG"
    fi
}
